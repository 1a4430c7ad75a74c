"""Runner for cells that serve a LOOPED language model (a stack of layers
run ``total_ut_steps`` times a token: ``configs/ouro-2.6b.json``) through the
repo's continuous-batching engine.

Everything a run does is ``runners/serve.py``'s: the engine built as
``serving.main`` builds it, the traffic, the clocks, ``failed`` and the
comparison that decides ``correct``.  That module binds the dense decoder's
reference, weights and key map when it is imported, so this runner loads a
private copy of it and rebinds those three names to the looped model's:
``lib/reference_looped.py``, ``lib/weights_looped.py`` and the map below,
which also hands the program its loop count, its norm eps and the switch
of the block that the configuration's ``assumed`` states.  No line
of the driving code is copied; the copy in ``sys.modules`` stays untouched.

A run leaves no process behind however it ends.  ``serve.py`` starts a child
that reads ``serving.main``'s defaults and waits for it only once the
program's tree has been checked, so a program that fails before that (one
that cannot loop a stack fails at its first unknown field) would leave the
child running after the run: ``run`` refuses such a program before anything
is started, and stops on its way out whatever child is still there.
"""

import dataclasses
import importlib.util
import pathlib
import subprocess

from benchmark.lib import reference_looped, weights_looped

_HERE = pathlib.Path(__file__).resolve().parent


def _serve():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_for_looped", _HERE / "serve.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.reference, module.weights = reference_looped, weights_looped
    module._FIELDS = {**module._FIELDS, "total_ut_steps": "loop_steps",
                      "rms_norm_eps": "norm_eps",
                      "sandwich_norm": "sandwich_norm"}
    return module


class _Children:
    """``subprocess`` for the private copy, keeping what ``Popen`` starts."""

    def __init__(self):
        self.started = []

    def __getattr__(self, name):
        return getattr(subprocess, name)

    def Popen(self, *args, **kwargs):  # noqa: N802  (subprocess's own name)
        self.started.append(subprocess.Popen(*args, **kwargs))
        return self.started[-1]


def run(ctx):
    from kubeflow_tpu.models.transformer import TransformerConfig

    module = _serve()
    known = {f.name for f in dataclasses.fields(TransformerConfig)}
    missing = sorted(set(module._FIELDS.values()) - known)
    if missing:
        raise SystemExit(f"this program's TransformerConfig has no {missing}: "
                         "it cannot run a looped model")
    children = module.subprocess = _Children()
    try:
        return module.run(ctx)
    finally:
        for child in children.started:
            if child.poll() is None:
                child.kill()
                child.communicate()
