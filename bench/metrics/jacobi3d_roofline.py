"""jacobi3d_roofline: share of the HBM roofline the jacobi3d kernels reach.

Bytes: each call's distinct operands and results (``bench.kernel_bytes``)
over the solves of the traced window, per chip.  Time: the device time of
the ops whose name matches the cell configuration's ``kernel_pattern``.
The calls counted in the trace must equal the calls the solves made;
otherwise the ops cannot be attributed and nothing is returned.
"""
import sys

from bench import kernel_bytes as kb


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    cfg, rec = ctx.config, ctx.record
    block = kb.block_shape(int(cfg["n"]), cfg["mesh"])
    want = {"sweep": 0, "residual": 0}
    for s in rec["solves"]:
        for kind, c in kb.calls_per_solve(ctx.traffic["reduction"],
                                          s["outer_iters"],
                                          s["verifications"]).items():
            want[kind] += c
    need = sum(c * kb.call_bytes(kind, block) for kind, c in want.items())
    total_ns, ideal_ns = 0.0, 0.0
    for dev in ctx.trace.devices:
        ns, calls = ctx.trace.kernel_ns(dev, cfg["kernel_pattern"])
        if calls != sum(want.values()):
            print(f"jacobi3d_roofline: {dev} shows {calls} kernel ops, the "
                  f"solves made {sum(want.values())}", file=sys.stderr)
            return None
        total_ns += ns
        ideal_ns += need / ctx.peak("hbm_bytes_per_s") * 1e9
    return 100.0 * ideal_ns / total_ns if total_ns > 0 else None
