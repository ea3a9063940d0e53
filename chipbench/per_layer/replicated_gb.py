"""Gigabytes a fit lays out whole on every chip of the mesh: the program's
``bytes_replicated`` counter (``placement_stats()["mesh"]``), mean over the
window's fits.  Nothing to read on a program without the counter."""


def read(ctx):
    moved = [r["counters"].get("mesh_bytes_replicated")
             for r in ctx["records"]]
    if not moved or any(m is None for m in moved):
        return None
    return sum(moved) / len(moved) / 1e9
