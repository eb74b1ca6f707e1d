"""held_share.<mix>: % of the (token, expert) pairs routed in the traced
window's decode steps that landed on the experts held here: the growth of
the server's ``held_rows`` counter over that of ``routed_rows`` between the
window's first and last ``serve.step`` span (``expert_counts``); none for a
program that does not count routes."""
import expert_counts


def read(run, metric):
    g = expert_counts.growth(run.trace, ("held_rows", "routed_rows"))
    if not g or g["routed_rows"] <= 0:
        return None
    return 100.0 * g["held_rows"] / g["routed_rows"]
