"""Scheduling policies for replica selection (paper Sec. IV, Algorithm 1).

All three policies return a probability distribution over the devices of
one group/layer, restricted to the currently *available* devices (active
and queue-empty). The same code runs on host arrays with NumPy (the
serving router, which must not touch the device per admission) and traced
with ``jax.numpy`` (inside the jitted network simulator).

* ``uniform``   — 1/|available| over available devices.
* ``long_term`` — Eq. (6): ``r_i = q_lim,i / sum_j q_lim,j`` over available.
* ``adaptive``  — Alg. 1 lines 20-28: start from long-term, scale every
  device currently in the critical power mode PM1 by ``z = alpha/N_l``
  (``alpha`` defaults to the number of PM1 devices), re-normalize.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "uniform_probs",
    "long_term_probs",
    "adaptive_probs",
    "POLICIES",
    "POLICY_LIST",
    "POLICY_IDS",
]

_EPS = 1e-12


def _xp(*arrays):
    """NumPy when every input is a host array, else ``jax.numpy``."""
    return np if all(isinstance(a, np.ndarray) for a in arrays) else jnp


def _masked_normalize(x, mask):
    xp = _xp(x, mask)
    x = xp.where(mask, x, 0.0)
    total = xp.sum(x)
    n_avail = xp.sum(mask.astype(x.dtype))
    # Fall back to uniform-over-available if all mass was zeroed out.
    fallback = xp.where(mask, 1.0, 0.0) / xp.maximum(n_avail, 1.0)
    return xp.where(total > _EPS, x / xp.maximum(total, _EPS), fallback)


def uniform_probs(q_lims, pm, available):
    """Uniform over available devices (q_lims/pm unused, kept for API parity)."""
    del q_lims, pm
    xp = _xp(available)
    mask = available.astype(xp.float32)
    return mask / xp.maximum(xp.sum(mask), 1.0)


def long_term_probs(q_lims, pm, available):
    """Eq. (6) restricted to available devices."""
    del pm
    xp = _xp(q_lims, available)
    return _masked_normalize(xp.asarray(q_lims, dtype=xp.float32), available)


def adaptive_probs(q_lims, pm, available, alpha=None):
    """Algorithm 1 ``ADAPTIVE``: down-weight critical-mode (PM1) devices.

    ``pm`` is each device's *current* active power mode index (1-based);
    devices in PM1 (the lowest-energy mode) get their long-term rate scaled
    by ``z = alpha / N_l`` and the vector is re-normalized.
    """
    xp = _xp(q_lims, pm, available)
    x = long_term_probs(q_lims, None, available)
    pm = xp.asarray(pm)
    critical = (pm == 1) & available
    n_l = x.shape[-1]
    if alpha is None:
        alpha = xp.sum(critical.astype(xp.float32))
    z = alpha / n_l
    x = xp.where(critical, x * z, x)
    return _masked_normalize(x, available)


POLICIES = {
    "uniform": uniform_probs,
    "long_term": long_term_probs,
    "adaptive": adaptive_probs,
}

# Signature-uniform ordering for traced dispatch: the simulator selects a
# policy at runtime via ``jax.lax.switch(policy_id, ...)`` over this tuple,
# so a sweep can mix policies inside one compiled executable. All three
# share the positional signature ``(q_lims, pm, available) -> probs``.
POLICY_LIST = (uniform_probs, long_term_probs, adaptive_probs)
POLICY_IDS = {name: POLICY_LIST.index(fn) for name, fn in POLICIES.items()}
