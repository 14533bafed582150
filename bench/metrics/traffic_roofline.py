"""Share of its roofline that the traffic engine's weighted counting
products reach in the traced window.

The demand-weighted ECMP loop makes two stacked counting products per BFS
level and matrix; the program's ``traffic.scenario`` span counts them
(``products``, 2 x diameter x samples). Each n x n by n x n product is
2 n^3 operations and 12 n^2 bytes (two float32 operands read once, the
product written once), n the unpadded router count, as
`counting_roofline` reckons them (its functions, imported). The least
time is the larger of operations over the chip's int8 peak and bytes over
its HBM bandwidth.
"""
import re

from bench.devtrace import tpu_kernel_operands
from bench.metrics.counting_roofline import bytes_moved, operations

#: the stacked counting product is a float32 Pallas custom call with two
#: operands and a batch axis, ``%body.N = f32[2,3456,3456]{..}
#: custom-call(f32[..] %a, f32[..] %b)``; the unbatched counting product
#: and the three-operand frontier step do not match
BATCHED = re.compile(r" = f32\[\d+,\d+,\d+\]")
OPERANDS = 2


def is_kernel(name: str) -> bool:
    return (name.startswith("%") and BATCHED.search(name.split("(", 1)[0])
            is not None and tpu_kernel_operands(name) == OPERANDS)


def products(ctx) -> int:
    return sum(int(a.get("products", 0))
               for a in ctx.span_attrs("traffic.scenario"))


def read(ctx):
    if ctx.device_ops is None:
        return None
    window = [e for e in ctx.device_ops if ctx.trace_lo <= e[1] < ctx.trace_hi]
    seconds = sum(d for name, _, d in window if is_kernel(name)) / 1e9
    count = products(ctx)
    if seconds <= 0 or count <= 0:
        return None
    n = ctx.config["routers"]
    least = max(operations(n, count) / ctx.peaks["int8_ops_per_s"],
                bytes_moved(n, count) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
