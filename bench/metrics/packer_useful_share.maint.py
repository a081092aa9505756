"""Real (column, row group) cells over padded B x R cells of packs built, %.

The program's own count, `ndv_pack_cells_total{cell="real"|"padded"}`, over
every pack the packer built in the window. The in-program twin of
`pack_useful_share.maint`, which the harness counts over the packs the engine
dispatched.
"""


def read(ctx):
    cells = {labels.get("cell"): d
             for labels, d in ctx["series"].get("ndv_pack_cells_total", ())}
    if not cells.get("padded"):
        return None
    return 100.0 * cells.get("real", 0.0) / cells["padded"]
