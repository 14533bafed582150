"""What the program's leaf spans say about a design point.

The program names each leaf span ``<stage>.<part>``: ``host`` (host
compute), ``h2d`` and ``d2h`` (a transfer, ending when the bytes have
arrived), ``wait`` (the host blocked on the device). Transfers carry their
bytes as ``h2d_bytes`` / ``d2h_bytes`` attributes, and any span the JAX
tracing, lowering and compile seconds that ran inside it as ``jit_s``.
The readers below sum them per design point, and return None for a
program that has no such spans.
"""

PARTS = ("host", "h2d", "d2h", "wait")


def part(name: str) -> str:
    return name.rpartition(".")[2] if "." in name else ""


def instrumented(ctx) -> bool:
    return bool(ctx.units) and any(part(n) in PARTS for n, _, _, _ in ctx.spans)


def seconds(ctx, *parts):
    """Seconds per design point in the leaf spans of ``parts``."""
    if not instrumented(ctx):
        return None
    return sum(d for n, _, d, _ in ctx.spans if part(n) in parts) / 1e9 / ctx.units


def attribute(ctx, *keys):
    """Sum per design point of the span attributes ``keys``."""
    if not instrumented(ctx):
        return None
    return sum(float(a.get(k, 0)) for _, _, _, a in ctx.spans for k in keys) / ctx.units
