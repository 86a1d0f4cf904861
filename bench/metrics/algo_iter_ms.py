"""Window time over the iterations the window's calls ran (algorithms:
``algorithms/bfs.py``, ``algorithms/pagerank.py``), from each result's
``n_iterations``."""


def read(ctx):
    it = ctx.facts.get("iterations")
    if not it:
        return None
    return ctx.window_s / it * 1e3
