"""The aggressor idle: the baseline lane of every size."""
from __future__ import annotations

import jax.numpy as jnp

PROGRAM = "no_congestion"


def envelope(t):
    return jnp.zeros_like(t)
