"""The aggressor at full blast all the time (paper §III-C)."""
from __future__ import annotations

import jax.numpy as jnp

PROGRAM = "steady"


def envelope(t):
    return jnp.ones_like(t)
