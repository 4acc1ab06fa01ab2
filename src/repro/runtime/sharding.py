"""Sharding rules: param/batch/cache PartitionSpecs for any mesh.

Strategy (MaxText-style 2-D/3-D sharding):
  * **fsdp** = ("pod", "data") when the pod axis exists, else ("data",):
    parameters, gradients and optimizer state shard their *d_model-like*
    dimension here (ZeRO-3), activations shard batch here;
  * **tensor** = "model": head/ffn/expert/vocab dimensions shard here
    (Megatron-style), contracting through psum/reduce-scatter;
  * any dimension not divisible by its axis size falls back to replication
    (e.g. kv_heads=8 on a 16-way tensor axis → shard head_dim instead).

Rules are keyed by parameter *leaf name* with symbols per trailing dim:
  D → fsdp, V/F/H/E → tensor, h/None → replicated. Leading (stacked-layer)
  dims are always None. Optimizer-state leaves (m/v/vr/vc) inherit the
  parent parameter's rule.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey, tree_flatten_with_path


def mesh_axes(mesh: Mesh, profile: str = "2d"):
    """profile "2d": fsdp over (pod, data) + tensor over "model".
    profile "fsdp_only": every axis joins the FSDP/batch group and tensor
    parallelism is disabled — the right shape for ≤10B-dense training,
    where TP's per-layer activation all-reduces dominate the collective
    roofline term (EXPERIMENTS.md §Perf, llama3-8b train hillclimb)."""
    names = mesh.axis_names
    if profile == "fsdp_only":
        return tuple(names), None
    fsdp = tuple(n for n in ("pod", "data") if n in names)
    tensor = "model" if "model" in names else None
    return fsdp, tensor


# symbol table: trailing-dim symbols per param leaf name
_RULES: Dict[str, Tuple] = {
    # embeddings / head
    "embed": ("V", "D"),
    "lm_head": ("D", "V"),
    "patch_proj": ("D", "F"),
    "frame_proj": ("D", "F"),
    # attention (GQA)
    "wq": ("D", "H", None),
    "wk": ("D", "H", None),
    "wv": ("D", "H", None),
    "wo": ("H", None, "D"),
    "bq": ("H", None),
    "bk": ("H", None),
    "bv": ("H", None),
    # attention (MLA)
    "wq_a": ("D", None),
    "wq_b": (None, "H", None),
    "wkv_a": ("D", None),
    "wk_rope": ("D", None),
    "wk_b": (None, "H", None),
    "wv_b": (None, "H", None),
    # mlp
    "gate": ("D", "F"),
    "up": ("D", "F"),
    "down": ("F", "D"),
    "router": ("D", None),
    # ssm / xlstm
    "in_proj": ("D", "F"),
    "out_proj": ("F", "D"),
    "up_proj": ("D", "F"),
    "down_proj": ("F", "D"),
    "conv_w": (None, "F"),
    "conv_b": ("F",),
    "wqkv": ("F", None, "H", None),
    "wif": ("F", None),
    "w_in": ("D", None, "H", None),
    "r": ("H", None, None, None),
    # scalars / vectors → replicated
    "scale": (None,),
    "A_log": (None,),
    "D": (None,),
    "dt_bias": (None,),
    "if_bias": (None,),
    "bias": (None, None, None),
}

# inside an "experts" subtree the leading expert dim shards on tensor and
# the ffn dim stays local (tensor axis already used by E)
_EXPERT_RULES = {
    "gate": ("E", "D", None),
    "up": ("E", "D", None),
    "down": ("E", None, "D"),
}

_SYMBOL_TO_AXIS = {"D": "fsdp", "V": "tensor", "F": "tensor", "H": "tensor",
                   "E": "tensor", None: None}


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(str(k.idx))
    return tuple(out)


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def _resolve(rule: Tuple, shape: Tuple[int, ...], mesh: Mesh,
             fsdp, tensor) -> P:
    """Trailing-dim rule → PartitionSpec with divisibility fallbacks."""
    ndim = len(shape)
    spec: list = [None] * ndim
    offset = ndim - len(rule)
    if offset < 0:           # rule longer than shape (e.g. squeezed bias)
        rule = rule[-ndim:]
        offset = 0
    used_tensor = False
    for i, sym in enumerate(rule):
        dim = offset + i
        kind = _SYMBOL_TO_AXIS.get(sym)
        if kind == "fsdp" and fsdp:
            if shape[dim] % _axes_size(mesh, fsdp) == 0:
                spec[dim] = fsdp if len(fsdp) > 1 else fsdp[0]
        elif kind == "tensor" and tensor and not used_tensor:
            if shape[dim] % _axes_size(mesh, tensor) == 0:
                spec[dim] = tensor
                used_tensor = True
    return P(*spec)


def spec_for_param(path_names: Tuple[str, ...], shape, mesh: Mesh,
                   profile: str = "2d") -> P:
    fsdp, tensor = mesh_axes(mesh, profile)
    names = [n for n in path_names if n not in ("m", "v", "f")]
    # optimizer-state leaves inherit the parent param rule
    leaf = names[-1] if names else ""
    if leaf in ("vr", "vc", "v", "error") and len(names) >= 2:
        parent = names[-2]
        rule = (_EXPERT_RULES.get(parent) if "experts" in names
                else None) or _RULES.get(parent)
        if rule is None:
            return P()
        if leaf == "vr":      # param minus last dim
            rule = rule[:-1]
        elif leaf == "vc":    # param minus second-to-last dim
            rule = rule[:-2] + rule[-1:]
        return _resolve(rule, shape, mesh, fsdp, tensor)
    if "experts" in names and leaf in _EXPERT_RULES:
        return _resolve(_EXPERT_RULES[leaf], shape, mesh, fsdp, tensor)
    rule = _RULES.get(leaf)
    if rule is None:
        return P()
    return _resolve(rule, shape, mesh, fsdp, tensor)


def infer_param_specs(params, mesh: Mesh, profile: str = "2d"):
    flat, treedef = tree_flatten_with_path(params)
    specs = [spec_for_param(_path_names(p), v.shape, mesh, profile)
             for p, v in flat]
    return jax.tree.unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

def spec_for_batch_leaf(name: str, shape, mesh: Mesh,
                        profile: str = "2d") -> P:
    fsdp, tensor = mesh_axes(mesh, profile)
    dp = fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None)
    dp_size = _axes_size(mesh, fsdp)
    if name == "positions3":         # (3, B, S)
        if shape[1] % dp_size == 0:
            return P(None, dp, None)
        return P()
    spec: list = [None] * len(shape)
    if shape and shape[0] % dp_size == 0 and shape[0] > 1:
        spec[0] = dp
    elif len(shape) >= 2 and shape[1] % dp_size == 0 and shape[1] > 1:
        spec[1] = dp                 # batch=1 → shard sequence (CP)
    return P(*spec)


def infer_batch_specs(batch, mesh: Mesh, profile: str = "2d"):
    flat, treedef = tree_flatten_with_path(batch)
    specs = [spec_for_batch_leaf(_path_names(p)[-1], v.shape, mesh, profile)
             for p, v in flat]
    return jax.tree.unflatten(treedef, specs)


_CACHE_HEAD_DIM = {"k": -2, "v": -2}


def spec_for_cache_leaf(name: str, shape, mesh: Mesh,
                        profile: str = "2d") -> P:
    """KV caches: (lead..., B, S, Hkv, Dh); states: (lead..., B, H, Dk, Dv);
    conv: (lead..., B, K, C); memory: (B, S, D); latents: (B, S, R)."""
    fsdp, tensor = mesh_axes(mesh, profile)
    dp = fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None)
    dp_size = _axes_size(mesh, fsdp)
    t_size = _axes_size(mesh, tensor) if tensor else 1
    ndim = len(shape)
    spec: list = [None] * ndim

    if name in ("k", "v"):            # (..., B, S, Hkv, Dh)
        b_dim, s_dim, h_dim, d_dim = ndim - 4, ndim - 3, ndim - 2, ndim - 1
        if shape[b_dim] % dp_size == 0 and shape[b_dim] > 1:
            spec[b_dim] = dp
        elif shape[s_dim] % dp_size == 0:
            spec[s_dim] = dp          # context-parallel long decode
        if tensor:
            if shape[h_dim] % t_size == 0:
                spec[h_dim] = tensor
            elif spec[s_dim] is None and shape[s_dim] % t_size == 0:
                # kv_heads < tensor axis: shard the sequence instead
                # (flash-decode; matches _sdpa's decode constraints)
                spec[s_dim] = tensor
            elif shape[d_dim] % t_size == 0:
                spec[d_dim] = tensor
    elif name in ("ckv", "k_rope", "memory"):   # (..., B, S, R)
        b_dim, s_dim, r_dim = ndim - 3, ndim - 2, ndim - 1
        if shape[b_dim] % dp_size == 0 and shape[b_dim] > 1:
            spec[b_dim] = dp
        elif shape[s_dim] % dp_size == 0:
            spec[s_dim] = dp
        if tensor and name == "ckv" and shape[r_dim] % t_size == 0:
            spec[r_dim] = tensor
    elif name == "state":             # (..., B, H, Dk, Dv)
        b_dim, h_dim, k_dim = ndim - 4, ndim - 3, ndim - 2
        if shape[b_dim] % dp_size == 0 and shape[b_dim] > 1:
            spec[b_dim] = dp
        if tensor:
            if shape[h_dim] % t_size == 0:
                spec[h_dim] = tensor
            elif shape[k_dim] % t_size == 0:
                spec[k_dim] = tensor
    elif name == "conv":              # (..., B, K, C)
        b_dim, c_dim = ndim - 3, ndim - 1
        if shape[b_dim] % dp_size == 0 and shape[b_dim] > 1:
            spec[b_dim] = dp
        if tensor and shape[c_dim] % t_size == 0:
            spec[c_dim] = tensor
    elif name in ("c", "n", "h", "m"):  # slstm scalars (..., B, H, Dh)
        b_dim = ndim - 3
        if 0 <= b_dim and shape[b_dim] % dp_size == 0 and shape[b_dim] > 1:
            spec[b_dim] = dp
    return P(*spec)


def infer_cache_specs(caches, mesh: Mesh, profile: str = "2d"):
    flat, treedef = tree_flatten_with_path(caches)
    specs = [spec_for_cache_leaf(_path_names(p)[-1], v.shape, mesh, profile)
             for p, v in flat]
    return jax.tree.unflatten(treedef, specs)


def named(specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def logits_spec(mesh: Mesh, profile: str = "2d") -> P:
    fsdp, tensor = mesh_axes(mesh, profile)
    dp = fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None)
    return P(dp, None, tensor)
