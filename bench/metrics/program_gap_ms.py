"""program_gap_ms.<mix>: the traced window's device idle time between
program runs, per decode step, in ms: the device has finished one program
(the decode step, the argmax) and waits for the host to dispatch the next.
Read on the device's clock, from its ``XLA Modules`` line; with
``op_gap_ms`` it adds up to (window - busy) / decode steps, where decode
steps are the program's ``serve.dispatch`` spans (``programspans``).  None
without them."""
import programspans

programspans.install()


def read(run, metric):
    split = programspans.gap_ms_per_step(run.trace, run.chips)
    return split[1] if split else None
