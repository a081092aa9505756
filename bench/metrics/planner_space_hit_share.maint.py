"""Plan spaces reused over plan spaces looked up in the window, %.

The program's `planner_space_cache_total{result="hit"|"miss"}`, one lookup
per cold /cost: a hit reuses a cached candidate set, a miss builds one.
None when the window looked up none, or on a program without the cache.
"""


def read(ctx):
    counts = {labels.get("result"): d for labels, d in
              ctx["series"].get("planner_space_cache_total", ())}
    hits = counts.get("hit", 0.0)
    looked_up = hits + counts.get("miss", 0.0)
    return 100.0 * hits / looked_up if looked_up else None
