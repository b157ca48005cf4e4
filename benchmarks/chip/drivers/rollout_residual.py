"""Driver ``rollout_residual``: the ``rollout`` driver on a CNN with residual
side paths.

Everything is the ``rollout`` driver's (``drivers/rollout.py``): the
back-to-back ``FleetRollout.run`` calls, the window, the sampling and the
comparison, with the same traffic keys.  Only the deployment the reference
replays differs: its CNN is priced by ``residual_costs`` (side paths at
every cut inside a block, projections on the block's opening unit), where
``reference.Deployment.from_config`` prices a plain chain.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import residual_costs


def _load_rollout_driver():
    """A private copy of ``drivers/rollout.py``, so that the deployment it
    checks against can be swapped here without touching the original."""
    spec = importlib.util.spec_from_file_location(
        "bench_rollout_residual_base", Path(__file__).with_name("rollout.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _ResidualDeployment:
    """What the copy's ``check`` calls ``Deployment``."""

    @staticmethod
    def from_config(cfg: dict):
        return residual_costs.deployment(cfg)


_base = _load_rollout_driver()
_base.Deployment = _ResidualDeployment
SPAN = _base.SPAN
Driver = _base.Driver
