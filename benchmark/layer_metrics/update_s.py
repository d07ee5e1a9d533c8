"""``update_s.<coordinate>``: mean ``train_seconds`` of that coordinate's
``CoordinateUpdate`` events inside the window, the traced sweep left out."""


def read(name, ctx):
    coordinate = name.split(".", 1)[1]
    s = [u["train_seconds"] for u in ctx["updates"]
         if u["coordinate"] == coordinate
         and u["iteration"] >= ctx["setup_sweeps"]
         and u["iteration"] != ctx["traced_sweep"]]
    return sum(s) / len(s) if s else None
