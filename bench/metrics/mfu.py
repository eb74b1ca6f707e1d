"""mfu.<mix>: model FLOPs of the tokens the decode steps processed in the
traced window, over window x chips x peak bf16 FLOP/s, in %.  Each active
slot processes one token per step; its FLOPs come from the family's count
function at that slot's context (``bench/families/<family>.py``), averaged
over the client's steps in the traced interval and multiplied by the steps
the trace holds."""
import tracereduce


def read(run, metric):
    t = run.trace
    on, off = run.window.trace_interval
    steps = [s for s in run.window.steps if on <= s.start < off]
    if not t or not t.devices or not steps or t.window_s <= 0:
        return None
    f = run.family.flops_per_token
    per_step = sum(f(run.c, ctx) for s in steps for ctx in s.contexts) \
        / len(steps)
    flops = per_step * tracereduce.steps_in(t)
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / (t.window_s * peak)
