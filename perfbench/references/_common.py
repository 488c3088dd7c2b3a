"""What the plain references share: layer widths and the matmul."""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> List[Tuple[int, int]]:
    d = [cfg["feat_dim"]] + [cfg["hidden"]] * (cfg["n_layers"] - 1) \
        + [cfg["n_classes"]]
    return list(zip(d[:-1], d[1:]))


PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def matmul(a, b, dtype, precision: str):
    """``a @ b`` at the named matmul precision (on a TPU, ``"default"``
    rounds fp32 operands to bfloat16 and accumulates in fp32), with its
    result in ``dtype``."""
    return jnp.matmul(a, b, precision=PRECISIONS[precision],
                      preferred_element_type=dtype)
