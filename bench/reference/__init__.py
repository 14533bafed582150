"""Plain references for the benchmark's comparison.

Nothing here imports the program under test. Each module is a
straightforward implementation of what the program computes:

* `fabrics`   builds each configuration's router graph from its published
  construction (one module per family), with the program's vertex
  numbering so adjacencies compare cell by cell;
* `counts`    hop distances, shortest-path multiplicities, +1/+2 slack
  simple-path counts and ECMP link loads, as plain `jax.numpy` products at
  HIGHEST precision on the device;
* `reports`   the report's reductions, the spectral power iterations in
  float64 and the construction cost and power model.
"""
