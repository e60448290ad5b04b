"""Search (``search/unity.py``): seconds inside ``FFModel.compile``
(the Unity search, the mesh and the parameters' initialisation), before
XLA compiles anything. Part of ``setup_s``."""


def read(ctx):
    t = ctx.get("train")
    return t["search_s"] if t else None
