"""Loaded at start-up by the benchmark's ENGINE children only (run.py puts
this directory first on their PYTHONPATH and nowhere else).

``python -m production_stack_tpu.server.api_server`` has no flag for
``EngineConfig.load_format`` or ``EngineConfig.seed``, and a model
directory without a checkpoint is refused unless ``load_format`` is
``"dummy"``. The benchmark serves a public ``config.json`` with weights made
on the device from ``--seed``, so it sets those two fields, which the
program already has, as defaults. Nothing else of the program is altered.
PERF.md (Open questions) asks for the two flags; this file goes when they
exist.
"""

import os


def _install() -> None:
    seed = os.environ.get("CHIP_BENCH_WEIGHT_SEED")
    if seed is None:
        return
    from production_stack_tpu.engine.config import EngineConfig

    init = EngineConfig.__init__

    def with_seeded_dummy_weights(self, *args, **kwargs):
        kwargs.setdefault("load_format", "dummy")
        kwargs.setdefault("seed", int(seed))
        init(self, *args, **kwargs)

    EngineConfig.__init__ = with_seeded_dummy_weights


_install()
