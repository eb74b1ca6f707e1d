"""dispatch_ms.<mix>: host time in the program's ``serve.dispatch`` span per
decode step in the traced window, in ms: the two host-to-device copies and
the dispatch of the decode step and of the argmax, all asynchronous.  Read
on the host's clock (``programspans``); none without the span."""
import programspans

programspans.install()


def read(run, metric):
    return programspans.host_ms_per_step(run.trace, ("serve.dispatch",))
