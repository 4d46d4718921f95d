"""NN building blocks + the parameter-spec system.

Params are flat dicts ``path -> jnp array``; every param is declared once
as a `ParamSpec` (shape, logical sharding axes, initializer).  The same
specs drive: real initialization (smoke tests / examples), abstract
initialization (`jax.eval_shape` for the dry-run — no allocation), the
sharding rules (`distributed/sharding.py` maps logical axes → mesh axes),
and the parameter-count roofline terms.

Compute dtype policy: params are stored f32 (optimizer master), cast to
bf16 at use; matmuls accumulate f32 via ``preferred_element_type``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # logical axis names, len == ndim
    init: str = "normal"                # normal | zeros | ones | embed
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


Specs = Dict[str, ParamSpec]


def init_params(specs: Specs, key: jax.Array, dtype=jnp.float32):
    """Materialize params (used by smoke tests/examples; the dry-run uses
    eval_shape over this same function)."""
    keys = jax.random.split(key, max(len(specs), 1))
    out = {}
    for (path, spec), k in zip(sorted(specs.items()), keys):
        if spec.init == "zeros":
            out[path] = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            out[path] = jnp.ones(spec.shape, dtype)
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            std = spec.scale / np.sqrt(max(fan_in, 1))
            if spec.init == "embed":
                std = spec.scale * 0.02
            out[path] = (jax.random.normal(k, spec.shape, dtype) * std)
    return out


def abstract_params(specs: Specs, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins (dry-run path: zero allocation)."""
    return {p: jax.ShapeDtypeStruct(s.shape, dtype)
            for p, s in specs.items()}


def logical_axes(specs: Specs):
    return {p: s.axes for p, s in specs.items()}


# --------------------------------------------------------------------------
# primitive layers (pure functions; weights passed in, bf16 compute)
# --------------------------------------------------------------------------

def cast_bf16(x):
    return x.astype(jnp.bfloat16)


def dense(x, w, bias=None):
    """x [..., in] @ w [in, out] in bf16, f32 accumulation."""
    y = jnp.einsum("...i,io->...o", cast_bf16(x), cast_bf16(w),
                   preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return cast_bf16(y)


def rms_norm(x, gamma, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return cast_bf16(xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32))


def swiglu(x, w_gate, w_up, w_down):
    g = dense(x, w_gate)
    u = dense(x, w_up)
    return dense(jax.nn.silu(g.astype(jnp.float32)).astype(jnp.bfloat16) * u,
                 w_down)


def embed_lookup(table, tokens):
    return cast_bf16(jnp.take(table, tokens, axis=0))


def constrain(x, *axes):
    """with_sharding_constraint by mesh-axis name; silently skipped when
    the named axes aren't in the ambient mesh (smoke tests, 1-device)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    entries = []
    for a in axes:
        if a is None:
            entries.append(U)
        elif isinstance(a, tuple):
            present = tuple(n for n in a if n in mesh.axis_names)
            entries.append(present if present else U)
        else:
            entries.append(a if a in mesh.axis_names else U)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*entries))


def unembed(x, table):
    """Logits in f32 (stable CE), forced vocab-sharded over `model` so the
    [B, S, V] tensor (and its grad) never materializes replicated."""
    y = jnp.einsum("...d,vd->...v", cast_bf16(x), cast_bf16(table),
                   preferred_element_type=jnp.float32)
    return constrain(y, *((None,) * (y.ndim - 1)), "model")


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x [S, ..., hd] with positions [S]; head axes (if any) sit between S
    and hd and broadcast. Paired-halves rotation convention."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * freqs      # [S, hd/2]
    # insert broadcast axes for any head dims between S and hd
    while angles.ndim < x.ndim:
        angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return cast_bf16(out)
