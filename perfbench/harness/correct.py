"""The numbers that decide ``correct``, each held to its own limit."""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp


@jax.jit
def _gap_and_scale(y, y_ref):
    return (jnp.max(jnp.abs(y.astype(jnp.float32)
                            - y_ref.astype(jnp.float32))),
            jnp.max(jnp.abs(y_ref.astype(jnp.float32))))


def rel_err(y, y_ref) -> float:
    """``max |y - y_ref| / max |y_ref|``: the widest gap as a share of
    the output's scale.  Non-finite outputs read ``inf``."""
    if tuple(y.shape) != tuple(y_ref.shape):
        return math.inf
    gap, scale = (float(v) for v in _gap_and_scale(y, y_ref))
    if not (math.isfinite(gap) and math.isfinite(scale)):
        return math.inf
    return gap / max(scale, 1e-30)


class Comparison:
    """Numbers compared against limits; ``correct`` when each is at or
    under its limit."""

    def __init__(self, limits: Dict[str, float]) -> None:
        self.limits = limits
        self.values: Dict[str, float] = {}

    def add(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for {name!r}")
        self.values[name] = float(value)

    @property
    def correct(self) -> bool:
        return bool(self.values) and set(self.values) == set(self.limits) \
            and all(v <= self.limits[k] for k, v in self.values.items())

    def lines(self):
        for k in self.limits:
            v = self.values.get(k)
            yield f"compared {k} {_fmt(v)} limit {self.limits[k]!r}"

    def as_json(self) -> Dict[str, Dict[str, Optional[float]]]:
        return {k: {"value": _num(self.values.get(k)),
                    "limit": self.limits[k]} for k in self.limits}


def _num(v: Optional[float]) -> Optional[float]:
    return v if v is not None and math.isfinite(v) else None


def _fmt(v: Optional[float]) -> str:
    return "missing" if v is None else repr(v)

