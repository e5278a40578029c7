"""Feed-forward blocks: gated (swiglu/geglu) and plain (gelu/relu^2)."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import activate, dense_init, linear, shard_act

GATED = ("swiglu", "geglu")


def mlp_init(rng, d_model: int, d_ff: int, activation: str,
             dtype=jnp.float32, stack: Tuple[int, ...] = ()) -> Dict[str, Any]:
    ks = jax.random.split(rng, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype, stack),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype, stack)}
    if activation in GATED:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype, stack)
    return p


@jax.named_scope("mlp")
def mlp(p: Dict[str, Any], h: jnp.ndarray, activation: str) -> jnp.ndarray:
    up = linear(h, p["w_up"])
    if activation in GATED:
        up = activate(linear(h, p["w_gate"]), activation) * up
    else:
        up = activate(up, activation)
    up = shard_act(up, ("batch", "seq", "ff"))
    return linear(up, p["w_down"])
