"""KV pool (``serving/page_pool.py``): pages in use (live requests and
the prefix cache, the scratch page left out) over the arena's pages,
in percent, the mean over the window's step records.  Lower is better:
the same rows held in a smaller share of the arena leave room for more
rows before the pool evicts; a page leak reads higher."""
import records


def read(run):
    got = records.window(run)
    if got is None:
        return None
    used = [100.0 * r.counters["pages_used"] / r.counters["pages_total"]
            for r in got[1] if r.counters.get("pages_total")]
    return sum(used) / len(used) if used else None
