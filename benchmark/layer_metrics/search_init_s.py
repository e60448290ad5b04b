"""Search's neighbours inside ``FFModel.compile`` (``model.py``): the mesh
(span ``ff.startup.mesh``), building the executor (``.executor``) and
the parameters' initialisation (``.param_init``: host seconds, the
initialisers' programs compile or load and are dispatched; the device
may still be filling). ``search_s`` times all of ``FFModel.compile``:
this and the two ``search_*_s`` beside it are its parts. None before
the program's PR 50."""
from benchmark import startup


def read(ctx):
    return startup.phase_seconds(ctx, ["mesh", "executor", "param_init"])
