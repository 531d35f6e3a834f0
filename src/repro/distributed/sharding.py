"""Parameter/activation PartitionSpecs: the Megatron mapping, path-matched.

Column-parallel into the block, row-parallel out — one logical all-reduce
per block, inserted by GSPMD:

  embed table [V, d]          -> (model, None)        vocab-parallel
  head        [d, V]          -> (None, model)
  wq/wk/wv    [d, H*hd]       -> (None, model)        heads sharded
  wo          [H*hd, d]       -> (model, None)
  ffn_wi      [d, ff]         -> (None, model)
  ffn_wo      [ff, d]         -> (model, None)
  moe wi/wo   [E, ., .]       -> (model, None, None)  expert-parallel
  ssm in/up   [d, proj]       -> (None, model)
  ssm out/down[proj, d]       -> (model, None)
  norms/bias/vectors          -> replicated

Stacked layer dims (repeats, count) prepend None.  Batch inputs shard over
the DP axes (pod folds into data); vocab/MoE/TP all live on "model".
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


_RULES = {
    # leaf name -> base spec (without leading stack dims)
    "table": ("model", None),
    "head": (None, "model"),
    "wq": (None, "model"),
    "wk": (None, "model"),
    "wv": (None, "model"),
    "bq": ("model",),
    "bk": ("model",),
    "bv": ("model",),
    "wo": ("model", None),
    "ffn_wi": (None, "model"),
    "ffn_wo": ("model", None),
    "router": (None, None),
    "wi": ("model", None, None),     # MoE experts
    "in_proj": (None, "model"),
    "out_proj": ("model", None),
    "conv_w": (None, "model"),
    "conv_b": ("model",),
    "out_norm": ("model",),
    "up": (None, "model"),
    "down": ("model", None),
    "wgate": ("model", None),
    "wx": (None, "model"),
    "out": ("model", None),
    "r": (None, None, None),
}
# MoE wo [E, ff, d] collides with attention "wo" by name; disambiguated by rank.
_MOE_WO = ("model", None, None)


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def spec_for(path, leaf, fsdp: bool = True) -> P:
    name = _leaf_name(path)
    in_moe = any(
        isinstance(e, jax.tree_util.DictKey) and str(e.key) == "moe" for e in path
    )
    base = _RULES.get(name)
    if name == "wo" and in_moe:
        base = _MOE_WO
    if name == "wi" and not in_moe:
        base = None
    if base is None:
        return P()  # replicated (norms, scalars, A_log, D, ...)
    extra = leaf.ndim - len(base)
    if extra < 0:
        return P()
    base = list(base)
    # FSDP: additionally shard one free dim of every >=2D weight over "data"
    # (ZeRO-3 via GSPMD: params gather per layer inside the scan).  The
    # embedding table and LM head are exempt — their free dim feeds the
    # vocab-parallel gather/psum pattern and replicating d there costs only
    # ~vocab*d/|model| per device.
    # divisibility guard: the production mesh has |data|=|model|=16; a named
    # axis on a non-divisible dim is a pjit error (e.g. mLSTM block-diagonal
    # [G, 4, 4] projections) — drop to replicated for that dim
    for i, b in enumerate(base):
        if b is not None and leaf.shape[extra + i] % 16 != 0:
            base[i] = None
    if fsdp and name not in ("table", "head") and leaf.ndim >= 2:
        for i, b in enumerate(base):
            if b is None and leaf.shape[extra + i] % 16 == 0:
                base[i] = "data"
                break
    return P(*((None,) * extra + tuple(base)))


def param_specs(params, fsdp: bool = True) -> dict:
    """Pytree of PartitionSpecs matching `params` (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: spec_for(p, l, fsdp), params
    )


def param_shardings(mesh, params):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params)
    )


def batch_spec(mesh) -> P:
    """Token batches: sharded over every DP axis."""
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return P(dp, None)


def maybe_constrain(x, *spec):
    """with_sharding_constraint that no-ops outside a mesh context.

    Model code calls this to pin activation layouts (EP dispatch buffers,
    attention intermediates) when compiled under a mesh; smoke tests and
    single-device runs pass through untouched.  Axes named in `spec` that
    the ambient mesh lacks are dropped.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or not mesh.axis_names:
            return x
        cleaned = tuple(
            a if (a is None or a in mesh.axis_names) else None for a in spec
        )
        return jax.lax.with_sharding_constraint(x, P(*cleaned))
    except Exception:  # noqa: BLE001 — constraint is best-effort by design
        return x


def table_specs(state) -> dict:
    """HKV table state: buckets sharded over 'model', clock/epoch replicated.

    Used by the replicated-over-data layout (vocab-parallel analogue); the
    all-to-all layout in distributed.table_sharding shards over all axes.
    """
    from repro.core.table import HKVState

    return HKVState(
        key_hi=P("model", None),
        key_lo=P("model", None),
        digests=P("model", None),
        score_hi=P("model", None),
        score_lo=P("model", None),
        values=P("model", None),
        clock_hi=P(),
        clock_lo=P(),
        epoch=P(),
    )


def decode_state_specs(mesh, state_shapes, kv_heads_divisible: bool) -> object:
    """KV caches: shard heads over model when divisible, else the sequence
    dim (decode-SP: GSPMD turns softmax reductions into partial+all-reduce).
    Recurrent SSM states shard batch over data and heads/channels over model."""

    def spec(path, leaf):
        name = _leaf_name(path)
        if name in ("k", "v"):      # [stack..., B, S, Hkv, dh] KV cache
            lead = leaf.ndim - 4
            if kv_heads_divisible:
                return P(*((None,) * lead), "data", None, "model", None)
            return P(*((None,) * lead), "data", "model", None, None)
        if name == "gla":           # [stack..., B, H, N, P] — shard the state
            # dim N (uniformly >= mesh model size: 64 for mamba2, 1024 for
            # mLSTM) rather than heads (xLSTM has only 4)
            lead = leaf.ndim - 4
            return P(*((None,) * lead), "data", None, "model", None)
        if name == "conv":          # [stack..., B, W, d_inner]
            lead = leaf.ndim - 3
            return P(*((None,) * lead), "data", None, "model")
        if name in ("c", "n", "h", "m"):  # sLSTM [stack..., B, H, pd]
            lead = leaf.ndim - 3
            return P(*((None,) * lead), "data", None, "model")
        if name == "pos":
            return P()
        if leaf.ndim >= 1:
            return P(*((None,) * leaf.ndim))
        return P()

    return jax.tree_util.tree_map_with_path(spec, state_shapes)
