"""Router-tier server time per request over the window, in ms.

The program's request histogram (`ndv_http_request_seconds`, tier
``router``): sum over count of what the window added to its cells.
"""


def read(ctx):
    cells = [d for labels, d in ctx["series"].get("ndv_http_request_seconds",
                                                  ())
             if labels.get("tier") == "router"]
    count = sum(d["count"] for d in cells)
    if not count:
        return None
    return sum(d["sum"] for d in cells) / count * 1e3
