"""Helpers of the drivers: the program's configuration from a config file,
checks beside their limits, tail statistics, and programs whose HLO text
names a trace's operations."""

from __future__ import annotations

import numpy as np


def model_config(hf: dict):
    """The program's ``ModelConfig``, from the ``model`` group of the file."""
    from repro.models.config import ModelConfig
    return ModelConfig(**hf["model"])


def check(value, limit, rule: str = "<=") -> dict:
    """A number compared with its limit: ``<=`` or ``==`` (NaN fails both)."""
    ok = (value <= limit) if rule == "<=" else (value == limit)
    return {"value": value, "limit": limit, "rule": rule, "ok": bool(ok)}


def percentile(values, q: float) -> float:
    """The q-th percentile; NaN for no values."""
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


class Recorded:
    """A jitted program that notes the abstract arguments of its first
    call, so that its compiled HLO text can be had afterwards (from the
    compilation cache) to name the operations of a trace."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            import jax
            self.args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=getattr(x, "sharding", None)),
                args)
        return self.fn(*args)

    def hlo_text(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()
