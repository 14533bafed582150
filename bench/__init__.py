"""The chip benchmark of the EvalNet analysis toolchain (see ``run.py``)."""
