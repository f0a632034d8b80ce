"""wave_fill: rows served over bucket rows launched in the window's waves,
from the serving engine's ``wave_stats`` (each wave pads to its bucket)."""


def read(ctx):
    serve = ctx["counters"].get("serve")
    if not serve or not serve["bucket_rows"]:
        return None
    return 100.0 * serve["rows"] / serve["bucket_rows"]
