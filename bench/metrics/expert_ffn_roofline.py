"""expert_ffn_roofline.<mix>: share, in %, of the held-experts FFN kernel's
roofline over the traced window: max(FLOPs / peak FLOP/s, bytes / HBM
bandwidth) of the rows the decode steps routed to held experts
(``expert_counts.expert_ffn``, from the growth of the server's routing
counters) over the kernel's device time, each side per decode step.

The kernel's ops are found by ``KERNEL``: the Pallas call is named
``expert_ffn``, so the trace's ``XLA Ops`` line holds
``%expert_ffn.<n> = f32[B,D]{...} custom-call(...)``, one per layer and
step.  None where the trace holds no such op or the program does not count
routes.
"""
import counts
import expert_counts
import tracereduce

KERNEL = r"^%expert_ffn(\.\d+)? = "


def read(run, metric):
    t = run.trace
    if not t or not t.devices:
        return None
    secs, n = tracereduce.kernel_s(t, KERNEL)
    g = expert_counts.growth(t)
    if not secs or not n or not g or g["step"] <= 0:
        return None
    import jax.numpy as jnp
    c = run.c
    w = jnp.dtype(c["dtype"]).itemsize
    rows = jnp.dtype(c.get("act_dtype") or c["dtype"]).itemsize
    bound = counts.roofline_s(*expert_counts.expert_ffn(
        g["held_rows"], g["expert_loads"], d_model=c["d_model"],
        d_ff=c["d_ff"], weight_bytes=w, row_bytes=rows), run.peaks)
    # per decode step on each side: one kernel call per layer and step
    return 100.0 * (bound / g["step"]) / (secs / (n / c["layers"]))
