"""Test set-up shared by the benchmark's tests.

``test_control.py`` plants the control through ``build=`` for ``solve``
loops only, and otherwise patches the serving stepper's ``retire``. A
loop that never runs that stepper (``loops/dist_solve.py``) exports its
own ``control_build`` instead: here, every test module's
``control_retire`` plants that function in the loop's place while open
(each ``spec.loop`` load of the loop comes with it as its ``build``),
so the control's run of such a cell answers with the control.
"""
from __future__ import annotations

import contextlib

import pytest


@pytest.fixture(autouse=True)
def _plant_loop_control(request, monkeypatch):
    retire = getattr(request.module, "control_retire", None)
    if retire is None:
        return

    @contextlib.contextmanager
    def plant(seed, cfg, mix):
        from harness import spec
        load = spec.loop
        if not hasattr(load(mix["loop"]), "control_build"):
            with retire(seed, cfg, mix):
                yield
            return

        def planted(kind):
            mod = load(kind)
            if kind == mix["loop"]:
                mod.build = mod.control_build
            return mod

        with monkeypatch.context() as m:
            m.setattr(spec, "loop", planted)
            yield

    monkeypatch.setattr(request.module, "control_retire", plant)
