"""kv_read_share.<mix>: % of the KV cache that the decode_attention kernel
reads in the traced window's decode steps: the growth of the server's
``kv_blocks`` counter (the blocks read, each slot's ``ceil((pos + 1) /
bk)`` summed over the slots and steps) over that of ``kv_blocks_full``
(``B * S / bk`` a step, what a kernel reading every position would) between
the window's first and last ``serve.step`` span; none for a program that
does not count them."""
import expert_counts
import programspans

programspans.install()


def read(run, metric):
    # ``growth`` reads any of the span's counters, not only the routes
    g = expert_counts.growth(run.trace, ("kv_blocks", "kv_blocks_full"))
    if not g or g["kv_blocks_full"] <= 0:
        return None
    return 100.0 * g["kv_blocks"] / g["kv_blocks_full"]
