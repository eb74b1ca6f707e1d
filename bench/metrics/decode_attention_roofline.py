"""decode_attention_roofline.<mix>: share, in %, of the decode_attention
kernel's roofline over the traced window: max(FLOPs / peak FLOP/s, bytes /
HBM bandwidth) over the kernel's summed device time.  FLOPs and bytes are
what the algorithm needs for the active slots at their lengths
(``counts.decode_attention``), each step's bound taken on its own, so a kernel that reads masked positions
shows it as a lower share.

The kernel's ops are found by ``KERNEL``: on a TPU v5e under JAX 0.9 the
Pallas call appears in the trace's ``XLA Ops`` line as
``%decode_attention.<n> = f32[B,20,1,128]{...} custom-call(...)``, one per
layer and step (the HLO instruction takes the name of the jitted kernel
function).
"""
import counts
import tracereduce

KERNEL = r"^%decode_attention(\.\d+)? = "


def read(run, metric):
    t = run.trace
    if not t or not t.devices:
        return None
    secs, n = tracereduce.kernel_s(t, KERNEL)
    on, off = run.window.trace_interval
    steps = [s for s in run.window.steps if on <= s.start < off]
    n_traced = tracereduce.steps_in(t)
    if not secs or not n or not steps or not n_traced:
        return None
    c = run.c
    bound = sum(counts.roofline_s(*counts.decode_attention(
        s.contexts, n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"],
        head_dim=c["head_dim"], layers=c["layers"],
        kv_bytes=run.kv_bytes["k"], q_bytes=run.kv_bytes["k"]), run.peaks)
        for s in steps)
    # per step on each side: the client's steps and the trace's may differ
    # by one at the edges of the traced interval
    return 100.0 * (bound / len(steps)) / (secs / n_traced)
