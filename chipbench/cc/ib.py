"""InfiniBand rate control: the send window follows what drains
(hop-by-hop credits), and FECN/BECN marks cut it multiplicatively.

``update(c, r, mark, can_dec, follow, inc, hc, p)`` takes each flow's
window ``c`` and rate ``r``, whether its path is marked, whether its
decrease timer has run out, the follower's step ``follow``, the additive
increase ``inc``, the host caps ``hc`` and the lane's CC scalars ``p``;
it returns the new window and which flows decreased.
"""
from __future__ import annotations

import jax.numpy as jnp


def update(c, r, mark, can_dec, follow, inc, hc, p):
    f = lambda x: jnp.asarray(x, c.dtype)  # noqa: E731
    c2 = (f(1) - follow) * c + follow * jnp.maximum(
        r * f(p["follow_gain"]), f(p["min_rate_frac"]) * hc)
    dec = mark & can_dec
    return jnp.where(dec, c2 * f(p["md"]), c2 + inc), dec
