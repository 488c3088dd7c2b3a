"""Deprecated shim — the overlay executor now lives in ``repro.engine``.

``OverlayExecutor`` used to walk in-memory ``Program`` layer objects.
Execution is now *binary-driven* (``repro.engine.executor.BinaryExecutor``
interprets the decoded 128-bit instruction stream), so this class survives
only as a thin adapter: it wraps the old ``run(program, x)`` signature by
serializing the object-graph ``Program`` to its ISA binary + manifest once
and delegating every call to the binary path.  Weight rebinding on
``prog.model.weights`` between runs is honored (read live, as before),
but *structural* mutation of an already-compiled Program's layers is not
— the snapshot binary is replayed; recompile instead.  New code should
use::

    from repro.engine import Engine
    engine = Engine()
    prog = engine.compile(model, graph)
    y = engine.run(prog, x)
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.engine.executor import BinaryExecutor, ExecStats  # noqa: F401
from repro.engine.program import from_program

from .passes.kernel_map import Program


class OverlayExecutor:
    """Deprecated: use ``repro.engine.Engine`` instead."""

    def __init__(self, backend: str = "xla", overlap: bool = True,
                 interpret: bool = False) -> None:
        warnings.warn(
            "OverlayExecutor is deprecated; use repro.engine.Engine "
            "(binary-driven execution)", DeprecationWarning, stacklevel=2)
        self._executor = BinaryExecutor(backend=backend, overlap=overlap,
                                        interpret=interpret)
        self.ack = self._executor.ack
        self.overlap = overlap

    @property
    def stats(self) -> ExecStats:
        return self._executor.stats

    def run(self, prog: Program, x: jnp.ndarray,
            weights: Optional[Dict[str, np.ndarray]] = None) -> jnp.ndarray:
        view = getattr(prog, "_compiled_view", None)
        if view is None:
            view = from_program(prog)
            prog._compiled_view = view
        # The legacy executor read prog.model.weights live on every call;
        # keep that (the view's snapshot would go stale if a caller
        # rebinds entries of model.weights between runs).
        if weights is None:
            weights = prog.model.weights
        return self._executor.run(view, x, weights=weights)
