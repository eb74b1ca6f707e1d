"""op_gap_ms.<mix>: the traced window's device idle time inside program
runs, between the operations of one program, per decode step, in ms.
Read on the device's clock, from its ``XLA Modules`` line; with
``program_gap_ms`` it adds up to (window - busy) / decode steps
(``programspans``).  None without the program's spans."""
import programspans

programspans.install()


def read(run, metric):
    split = programspans.gap_ms_per_step(run.trace, run.chips)
    return split[0] if split else None
