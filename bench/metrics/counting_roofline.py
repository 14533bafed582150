"""Share of its roofline that the counting (+, x) product kernel reaches
in the traced window.

The kernel serves two stages. The +1/+2 slack counts make two products
(walks and bounces) per level for levels 1 .. diameter + 2; the all-pairs
ECMP pass makes two per level for levels diameter - 1 .. 0. The diameter
is the program's wavefront telemetry (``levels`` - 1, the last level finds
no new pair). Each n x n by n x n product is 2 n^3 operations and 12 n^2
bytes (two float32 operands read once, the product written once), n the
unpadded router count. The least time is the larger of operations over
the chip's int8 peak and bytes over its HBM bandwidth.
"""
from bench.devtrace import tpu_kernel_operands

#: the kernel's device ops, read by hand from a v5e trace: the counting
#: product is a float32 Pallas custom call with two operands, as
#: ``%_count_jit.1 = f32[3456,3456]{..} custom-call(.. %pad.0, .. %pad.2)``
#: in the slack counts and ``%body.12``/``%body.13`` inside the ECMP loop
OPERANDS = 2


def is_kernel(name: str) -> bool:
    return (name.startswith("%") and " = f32[" in name
            and tpu_kernel_operands(name) == OPERANDS)


def products(ctx) -> int:
    levels = [int(a.get("levels", 0))
              for a in ctx.span_attrs("wavefront.dist_mult")]
    if not levels:
        return 0
    diameter = max(levels) - 1
    return (len(ctx.span_attrs("analysis.multiplicities")) * 2 * (diameter + 2)
            + len(ctx.span_attrs("analysis.comparison")) * 2 * diameter)


def operations(n: int, count: int) -> float:
    return 2.0 * n ** 3 * count


def bytes_moved(n: int, count: int) -> float:
    return 12.0 * n * n * count


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
