"""repro.engine — the unified GraphAGILE engine API.

The paper's contract is: one fixed overlay (bitstream) + per-(model,
graph) instruction binaries.  This package is that contract in software:

  * :class:`Engine` — one overlay instance (tile geometry + kernel cache);
    ``compile`` / ``run`` / ``load`` / ``submit`` / ``serve``.
  * :class:`CompiledProgram` — the serialized unit: 128-bit ISA binary +
    weights/graph manifest; ``save``/``load`` round-trips ``.gagi`` files.
  * :class:`BinaryExecutor` — executes by decoding the binary; no IR
    objects on the hot path.

Quickstart::

    from repro.engine import Engine

    engine = Engine()                       # the overlay
    prog = engine.compile("b1", graph)      # GCN -> 128-bit binary
    y = engine.run(prog, x)                 # decode + execute
    prog.save("gcn.gagi")                   # serve it in a later session

The legacy ``repro.core.compiler.compile_model`` /
``repro.core.executor.OverlayExecutor`` entry points remain as thin
deprecated shims over this package.
"""
from .cache import LRUCache
from .compile_cache import enable_compile_cache
from .decoder import ExecutionPlan, LayerPlan, TilePlan, decode_binary
from .engine import (Engine, EngineStats, InferenceRequest,
                     InferenceResponse, graph_signature, model_signature,
                     stack_features, stack_graph_data)
from .executor import (BinaryExecutor, ExecStats, ResidentBudgetError,
                       derive_placement, derive_residency,
                       ensure_placement)
from .program import CompiledProgram, build_manifest, from_program

__all__ = [
    "Engine", "EngineStats", "InferenceRequest", "InferenceResponse",
    "CompiledProgram", "BinaryExecutor", "ExecStats",
    "ResidentBudgetError", "LRUCache",
    "derive_placement", "derive_residency", "ensure_placement",
    "ExecutionPlan", "LayerPlan", "TilePlan", "decode_binary",
    "build_manifest", "from_program", "graph_signature", "model_signature",
    "stack_features", "stack_graph_data", "enable_compile_cache",
]
