"""prompt_share.<mix>: % of the decode steps' slot work, between the traced
window's first and last ``serve.step`` span, spent feeding prompt tokens
one at a time: the growth of the server's ``prompt_tokens`` counter over
that of ``slot_steps``, read from the span's stats (``programspans``);
none without them."""
import programspans

programspans.install()


def read(run, metric):
    return programspans.prompt_share(run.trace)
