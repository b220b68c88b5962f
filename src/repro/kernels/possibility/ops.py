"""Public op: possibility weights with host-side gather preparation.

Defaults are the COMPILED paths: on TPU the Pallas kernel runs compiled;
elsewhere the call auto-falls back to the dense jnp oracle, which XLA
jit-compiles — the interpreter is never the default anywhere.  Pass
``use_pallas`` / ``interpret`` explicitly to pin a path (tests run the
Pallas kernel in interpret mode on CPU to keep it covered).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .kernel import possibility_weights_pallas
from .ref import possibility_weights_dense

_dense_jit = functools.partial(jax.jit, static_argnames=("offset",))(
    possibility_weights_dense)


def backend_supports_pallas() -> bool:
    """Compiled Pallas lowering of the possibility kernel: TPU only, the
    one backend it is compiled for (``tests/test_chip_compile.py``)."""
    return jax.default_backend() == "tpu"


def _prepare(dist, traffic, channels):
    us = channels[:, 0]
    ns = channels[:, 1]
    dist = np.asarray(dist, np.int32)
    du = dist[:, us]                     # (N, C)
    dn = dist[ns, :]                     # (C, N)
    dsn = dist[:, ns]                    # (N, C)
    t = np.asarray(traffic, np.float32)
    tn = t[:, ns]                        # (N, C)
    return (jnp.asarray(du), jnp.asarray(dn), jnp.asarray(dsn),
            jnp.asarray(tn), jnp.asarray(t), jnp.asarray(dist))


def possibility_weights(dist, traffic, channels,
                        use_pallas: bool | None = None,
                        interpret: bool | None = None,
                        offset: int = 1):
    """(W, W_drn) per channel — eq. 5/7 (``offset=1``) or the k-hop
    continuation predicate (``offset=2`` for consecutive pairs; W_drn is
    then meaningless and should be ignored).

    ``use_pallas=None`` resolves to the backend's compiled support;
    ``interpret=None`` resolves to compiled where supported and to the
    interpreter only when the Pallas path was explicitly requested on a
    backend that cannot compile it.
    """
    if use_pallas is None:
        use_pallas = backend_supports_pallas()
    if interpret is None:
        interpret = use_pallas and not backend_supports_pallas()
    du, dn, dsn, tn, t, d = _prepare(dist, traffic, channels)
    if use_pallas:
        return possibility_weights_pallas(du, dn, dsn, tn, t, d,
                                          offset=offset,
                                          interpret=interpret)
    return _dense_jit(du, dn, dsn, tn, d, t, offset=offset)
