"""Share of its roofline that the wavefront's fused frontier-step kernel
reaches in the traced window.

Work is the problem's, not the implementation's: one n x n by n x n
product per executed wavefront level (n the unpadded router count), 2 n^3
operations, and 16 n^2 bytes (frontier, adjacency and distance operands
read once, the masked float32 product written once). The level count is
the program's own telemetry (``levels`` of each ``wavefront.dist_mult``
span). The least time is the larger of operations over the chip's int8
peak and bytes over its HBM bandwidth; no exact form of the counting
product can read above 100%.
"""
from bench.devtrace import tpu_kernel_operands

#: the kernel's device ops, read by hand from a v5e trace: the fused
#: frontier step is the wavefront loop's only float32 Pallas custom call
#: with three operands (frontier, adjacency, distances), e.g.
#: ``%body.3 = f32[3456,3456]{..} custom-call(f32[..] %copy.22, f32[..]
#: %get-tuple-element.118, f32[..] ...), custom_call_target="tpu_custom_call"``
OPERANDS = 3


def is_kernel(name: str) -> bool:
    return (name.startswith("%") and " = f32[" in name
            and tpu_kernel_operands(name) == OPERANDS)


def products(ctx) -> int:
    return sum(int(a.get("levels", 0))
               for a in ctx.span_attrs("wavefront.dist_mult"))


def operations(n: int, count: int) -> float:
    return 2.0 * n ** 3 * count


def bytes_moved(n: int, count: int) -> float:
    return 16.0 * n * n * count


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
