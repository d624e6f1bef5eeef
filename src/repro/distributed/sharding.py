"""Per-tensor-kind sharding rules: DP/FSDP + TP + EP + pod axis.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod.  Conventions:

* **DP**: batch over ``("pod", "data")`` (when divisible).
* **FSDP**: every weight's d_model-like dim over ``"data"`` — XLA SPMD
  all-gathers per scan step and reduce-scatters grads (ZeRO-3 pattern).
* **TP**: head/ffn/expert dims over ``"model"``:
    - attention q/o projections TP'd iff num_heads %% tp == 0,
      k/v iff num_kv_heads %% tp == 0 (else replicated over 'model' —
      they are small precisely when kv count is small);
    - MLP d_ff over 'model';
    - MoE experts over 'model' (EP);
    - vocab over 'model' (turns the logits loss reduction into
      reduce-scatter + all-gather instead of a fat all-reduce).
* **Caches**: KV cache sequence dim over 'model' (head counts are rarely
  divisible), batch over DP when divisible; SSD state heads over 'model'.

Every rule is a function of (leaf path, leaf, config, mesh) so new
architectures compose without per-model hacks.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

Params = Any


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def dp_size(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


# --------------------------------------------------------------------------
# parameter rules
# --------------------------------------------------------------------------

# name -> (base_ndim, spec builder)
def _param_rule(name: str, ndim: int, cfg: ModelConfig, tp: int):
    heads_tp = cfg.num_heads % tp == 0
    kv_tp = (cfg.num_kv_heads % tp == 0) and cfg.num_kv_heads >= tp
    ssd_tp = cfg.ssm_d_inner % (tp * cfg.ssm_head_dim) == 0

    table: Dict[str, Tuple[int, Tuple]] = {
        # embeddings
        "tok": (2, ("model", "data")),
        "unembed": (2, ("data", "model")),
        "patch_proj": (2, ("data", None)),
        # attention
        "wq": (2, ("data", "model") if heads_tp else ("data", None)),
        "wo": (2, ("model", "data") if heads_tp else (None, "data")),
        "wk": (2, ("data", "model") if kv_tp else ("data", None)),
        "wv": (2, ("data", "model") if kv_tp else ("data", None)),
        # MLA
        "wq_a": (2, ("data", None)),
        "wq_b": (2, (None, "model") if heads_tp else (None, None)),
        "wkv_a": (2, ("data", None)),
        "wk_b": (2, (None, "model") if heads_tp else (None, None)),
        "wv_b": (2, (None, "model") if heads_tp else (None, None)),
        # dense mlp (2D) / moe experts (3D)
        "w_gate": (2, ("data", "model")),
        "w_up": (2, ("data", "model")),
        "w_down": (2, ("model", "data")),
        "router": (2, ("data", None)),
        # ssd
        "w_z": (2, ("data", "model") if ssd_tp else ("data", None)),
        "w_x": (2, ("data", "model") if ssd_tp else ("data", None)),
        "w_bc": (2, ("data", None)),
        "w_dt": (2, ("data", None)),
        "out_proj": (2, ("model", "data") if ssd_tp else (None, "data")),
    }
    # NOTE: MoE expert tensors (E,d,f) are routed in param_spec (which can
    # check the path for ffn_moe/router siblings); returning a MoE spec here
    # based on ndim alone mis-sharded stacked dense (L,d,f) weights.
    return table.get(name, None)


def _leading_pad(spec: Tuple, leaf_ndim: int, mesh: Optional[Mesh] = None) -> P:
    base = len(spec)
    pad = leaf_ndim - base
    if pad < 0:
        # scalar-ish leaf (e.g. rank cut by vmap) — replicate
        return P()
    spec = tuple(spec)
    if mesh is not None:
        # FSDP spans ALL data-parallel axes (pod included): at 405B-scale the
        # f32 master+moments only fit when ZeRO-sharded over the full DP set.
        dpa = dp_axes(mesh)
        spec = tuple((dpa if s == "data" and len(dpa) > 1 else s)
                     for s in spec)
    return P(*((None,) * pad + spec))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def validate_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on any dim the mesh axes don't divide (odd vocab sizes,
    head counts, raggeds) — correctness first; the roofline shows the cost
    of the resulting replication."""
    out = []
    for i, entry in enumerate(spec):
        if i >= len(shape) or entry is None:
            out.append(None)
            continue
        out.append(entry if shape[i] % _axis_size(mesh, entry) == 0 else None)
    return P(*out)


def param_spec(path, leaf, cfg: ModelConfig, mesh: Mesh) -> P:
    tp = tp_size(mesh)
    name = None
    for k in reversed(path):
        if hasattr(k, "key"):
            name = str(k.key)
            break
    if name is None:
        return P()
    ndim = np.ndim(leaf)

    # replicated small tensors
    if name in ("conv_w", "conv_b", "A_log", "D", "dt_bias", "norm_w",
                "q_norm", "kv_norm", "final_norm", "enc_final_norm") or \
            name.startswith("ln"):
        return P()

    # MoE expert tensors: path contains an 'ffn'/'ffn_moe'/'shared' marker
    in_moe = any(getattr(k, "key", None) in ("ffn", "ffn_moe") for k in path)
    in_moe = in_moe and not any(
        getattr(k, "key", None) == "dense_layers" for k in path)
    in_shared = any(getattr(k, "key", None) == "shared" for k in path)
    if in_moe and not in_shared and name in ("w_gate", "w_up", "w_down") \
            and cfg.moe_num_experts and cfg.moe_num_experts % tp == 0:
        spec = {"w_gate": ("model", "data", None),
                "w_up": ("model", "data", None),
                "w_down": ("model", None, "data")}[name]
        return _leading_pad(spec, ndim, mesh)

    rule = _param_rule(name, ndim, cfg, tp)
    if rule is None:
        return P()
    _, spec = rule
    return _leading_pad(tuple(spec), ndim, mesh)


def param_shardings(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    def one(path, leaf):
        spec = validate_spec(param_spec(path, leaf, cfg, mesh),
                             np.shape(leaf), mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params)


# --------------------------------------------------------------------------
# batch / cache rules
# --------------------------------------------------------------------------

def _dp_for_batch(batch_size: int, mesh: Mesh) -> Optional[Tuple[str, ...]]:
    axes = dp_axes(mesh)
    if not axes:
        return None
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if batch_size % n == 0:
        return axes
    # try data only
    if "data" in axes and batch_size % mesh.shape["data"] == 0:
        return ("data",)
    return None


def batch_shardings(batch: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    def one(leaf):
        b = np.shape(leaf)[0]
        axes = _dp_for_batch(b, mesh)
        spec = P(axes) if axes else P()
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(one, batch)


def cache_spec(path, leaf, cfg: ModelConfig, mesh: Mesh, batch: int) -> P:
    """KV caches: (L, B, S, KV, hd) -> B over DP (if divisible), S over
    'model'.  SSD state (L, B, H, P, N) -> H over 'model' when divisible.
    conv cache / small leaves replicated."""
    name = None
    for k in reversed(path):
        if hasattr(k, "key"):
            name = str(k.key)
            break
    ndim = np.ndim(leaf)
    tp = tp_size(mesh)
    daxes = _dp_for_batch(batch, mesh)
    bspec = daxes if daxes else None

    if name in ("k", "v") and ndim >= 4:
        shape = np.shape(leaf)
        s_ok = shape[-3] % tp == 0
        spec = (bspec, "model" if s_ok else None, None, None)
        return _leading_pad(spec, ndim)
    if name == "c_kv" or name == "k_rope":
        shape = np.shape(leaf)
        s_ok = shape[-2] % tp == 0
        spec = (bspec, "model" if s_ok else None, None)
        return _leading_pad(spec, ndim)
    if name == "ssm" and ndim >= 4:
        shape = np.shape(leaf)
        h_ok = shape[-3] % tp == 0
        spec = (bspec, "model" if h_ok else None, None, None)
        return _leading_pad(spec, ndim)
    if name == "conv" and ndim >= 3:
        spec = (bspec, None, None)
        return _leading_pad(spec, ndim)
    return P()


def cache_shardings(cache: Params, cfg: ModelConfig, mesh: Mesh,
                    batch: int) -> Params:
    def one(path, leaf):
        spec = validate_spec(cache_spec(path, leaf, cfg, mesh, batch),
                             np.shape(leaf), mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, cache)


# --------------------------------------------------------------------------
# activation constraint helper (used inside model code when mesh is set)
# --------------------------------------------------------------------------

def opt_state_shardings(opt_state, params_shardings) -> Any:
    """AdamW state mirrors param shardings (count replicated)."""
    from repro.optim.adamw import AdamWState
    mesh = jax.tree_util.tree_leaves(params_shardings)[0].mesh
    return AdamWState(
        count=NamedSharding(mesh, P()),
        master_lo=params_shardings,
        m=params_shardings,
        v=params_shardings,
    )
