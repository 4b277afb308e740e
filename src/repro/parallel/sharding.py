"""Logical-axis sharding rules (Swallow C1: explicit placement of every byte).

Model code annotates activations with *logical* axis names
(``logical_constraint(x, "batch", "seq", None)``); a rule table maps logical
names to physical mesh axes.  Outside a mesh context the annotations are
no-ops, so the same model runs on a single CPU device in tests.

Weight placement (Swallow C4 — every chip is both a compute node and a
storage node) is expressed the same way: ``param_specs`` assigns each
parameter leaf a PartitionSpec from its leaf name, giving 2-D
(FSDP x TP) sharding by default.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

# Baseline rule table (paper-faithful distributed-memory layout):
#   batch        -> farmer-worker axis (pod x data)
#   seq_sp       -> sequence-parallel residual stream (Megatron-SP)
#   heads/ffn/.. -> tensor-parallel "model" axis
#   fsdp         -> weight-shard storage axis (nodes-as-storage, C4)
DEFAULT_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_sp": "model",
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "vocab": "model",
    # MoE: baseline is expert-TP ("expert_ff" over model, experts unsharded —
    # works for any expert count); the EP alternative maps "expert" -> model.
    "expert": None,
    "expert_ff": "model",
    # expert weights stay 2-D sharded (explicitly gathered inside the MoE
    # shard_map); dense weights are TP-only — dry-run HLO attribution showed
    # GSPMD gathering full weight stacks per scan iteration under 2-D
    # sharding, and the fully-sharded flat optimizer state (ZeRO-1) makes
    # dense-weight FSDP unnecessary for memory at these scales.
    "fsdp": ("pod", "data"),
    "fsdp_dense": None,
    "tp": "model",
    "stage": "pod",
    # paged-KV serving: the page axis of the KV pools is striped over the
    # TP axis with the paper's address%n rule (core/memory_server
    # .stripe_slab_index maps logical page -> physical slab row so the
    # NamedSharding over this axis places every stripe on its owner node)
    "pages": "model",
}

# Serving rule table: ONLY the paged-KV pools are sharded.  Decode
# activations are batch=1-per-request and tiny; striping them over the
# training TP rules would either fail divisibility (data axis vs
# batch 1) or force weight gathers per token.  The paper's serving
# story is the *store* that is distributed (C4 nodes-as-storage): KV
# pages live on their striped_owner node, parameters and activations
# replicate, and the decode kernel's owner-partials merge is the only
# cross-node collective.
SERVING_RULES: Dict[str, Axis] = dict(
    {k: None for k in DEFAULT_RULES}, pages="model")


@dataclass(frozen=True)
class ShardingEnv:
    mesh: Mesh
    rules: Mapping[str, Axis] = field(default_factory=lambda: DEFAULT_RULES)

    def resolve(self, logical: Axis) -> Axis:
        """Map a logical axis name to mesh axes present in this mesh."""
        if logical is None:
            return None
        mapped = self.rules.get(logical, None) if isinstance(logical, str) else logical
        if mapped is None:
            return None
        if isinstance(mapped, str):
            mapped = (mapped,)
        present = tuple(a for a in mapped if a in self.mesh.axis_names)
        if not present:
            return None
        return present if len(present) > 1 else present[0]

    def spec(self, *logical_axes: Axis) -> P:
        return P(*(self.resolve(a) for a in logical_axes))

    def sharding(self, *logical_axes: Axis) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical_axes))


_ENV: contextvars.ContextVar[Optional[ShardingEnv]] = contextvars.ContextVar(
    "sharding_env", default=None)


def current_env() -> Optional[ShardingEnv]:
    return _ENV.get()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[Mapping[str, Axis]] = None):
    """Activate a sharding environment (and the jax mesh context)."""
    if mesh is None:
        yield None
        return
    env = ShardingEnv(mesh, dict(DEFAULT_RULES, **(rules or {})))
    tok = _ENV.set(env)
    try:
        with mesh:
            yield env
    finally:
        _ENV.reset(tok)


def logical_constraint(x, *logical_axes: Axis):
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    env = current_env()
    if env is None:
        return x
    return jax.lax.with_sharding_constraint(x, env.sharding(*logical_axes))


# ---------------------------------------------------------------------------
# Parameter placement
# ---------------------------------------------------------------------------
# Leaf-name -> logical axes, matched on the last path component.  When the
# actual leaf has more dims than the spec (stacked scan params), leading
# dims are unsharded.
PARAM_RULES: Sequence[Tuple[str, Tuple[Axis, ...]]] = (
    # embeddings / head: vocab striped over TP = the paper's address%n
    (r"embed_table$", ("tp", "fsdp_dense")),
    (r"head_w$", ("fsdp_dense", "tp")),
    # attention
    (r"wq$", ("fsdp_dense", "tp")),
    (r"wk$", ("fsdp_dense", "tp")),
    (r"wv$", ("fsdp_dense", "tp")),
    (r"wo$", ("tp", "fsdp_dense")),
    (r"(q_norm|k_norm)$", (None,)),
    # MLA
    (r"q_a$", ("fsdp_dense", None)),
    (r"q_b$", ("fsdp_dense", "tp")),
    (r"kv_a$", ("fsdp_dense", None)),
    (r"kv_b$", ("fsdp_dense", "tp")),
    (r"(q_a_norm|kv_a_norm)$", (None,)),
    # dense FFN
    (r"w_gate$", ("fsdp_dense", "tp")),
    (r"w_up$", ("fsdp_dense", "tp")),
    (r"w_down$", ("tp", "fsdp_dense")),
    # MoE (experts striped over TP axis = expert parallelism)
    (r"router_w$", ("fsdp", None)),
    (r"router_b$", (None,)),
    (r"e_gate$", ("expert", "fsdp", "expert_ff")),
    (r"e_up$", ("expert", "fsdp", "expert_ff")),
    (r"e_down$", ("expert", "expert_ff", "fsdp")),
    # RG-LRU / Griffin
    (r"conv_w$", (None, "tp")),
    (r"conv_b$", ("tp",)),
    (r"lru_in_(x|gate)$", ("fsdp_dense", "tp")),
    (r"lru_out$", ("tp", "fsdp_dense")),
    # block-diag gates are (heads, hd, hd) with heads=10 for recurrentgemma:
    # not divisible by TP=16, and small — replicate them
    (r"lru_(a_gate|x_gate)_w$", (None, None, None)),
    (r"lru_(a_gate|x_gate)_b$", (None, None)),
    (r"lru_a_param$", ("tp",)),
    # RWKV6 time-mix
    (r"rwkv_(wr|wk|wv|wg)$", ("fsdp_dense", "tp")),
    (r"rwkv_wo$", ("tp", "fsdp_dense")),
    (r"rwkv_mix_lora_a$", ("fsdp_dense", None, None)),
    (r"rwkv_mix_lora_b$", (None, None, "fsdp_dense")),
    (r"rwkv_decay_lora_a$", ("fsdp_dense", None)),
    (r"rwkv_decay_lora_b$", (None, "fsdp_dense")),
    (r"rwkv_(mix_base|decay_base|mix_x)$", (None,)),
    (r"rwkv_u$", ("tp", None)),
    (r"rwkv_ln_(scale|bias)$", (None,)),
    # RWKV6 channel-mix
    (r"rwkv_cm_wk$", ("fsdp_dense", "tp")),
    (r"rwkv_cm_wv$", ("tp", "fsdp_dense")),
    (r"rwkv_cm_wr$", ("fsdp_dense", None)),
    (r"rwkv_cm_mix_(k|r)$", (None,)),
    # norms & misc small
    (r"scale$", (None,)),
    (r"bias$", (None,)),
    (r"mtp_proj$", ("fsdp_dense", "tp")),
)




def _axis_size(env: ShardingEnv, resolved) -> int:
    if resolved is None:
        return 1
    axes = (resolved,) if isinstance(resolved, str) else resolved
    n = 1
    for a in axes:
        n *= env.mesh.shape[a]
    return n


def _leaf_spec(path: str, shape, env: ShardingEnv) -> P:
    ndim = len(shape)
    for pat, axes in PARAM_RULES:
        if re.search(pat, path):
            axes = tuple(axes)
            if len(axes) < ndim:  # stacked scan params: leading dims unsharded
                axes = (None,) * (ndim - len(axes)) + axes
            elif len(axes) > ndim:
                raise ValueError(f"spec {axes} too long for {path} ndim={ndim}")
            # drop axes that don't divide the dim (e.g. hubert vocab=504
            # over TP=16) — the leaf is then replicated on that dim
            resolved = [env.resolve(a) for a in axes]
            resolved = [r if shape[i] % _axis_size(env, r) == 0 else None
                        for i, r in enumerate(resolved)]
            return P(*resolved)
    return P()  # replicate by default


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(params, env: Optional[ShardingEnv] = None):
    """PartitionSpec pytree for a parameter pytree (by leaf-name rules)."""
    env = env or current_env()
    if env is None:
        return jax.tree_util.tree_map(lambda _: P(), params)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path_str(path), leaf.shape, env),
        params)


def param_shardings(params, env: Optional[ShardingEnv] = None):
    env = env or current_env()
    if env is None:
        raise RuntimeError("param_shardings requires an active ShardingEnv")
    return jax.tree_util.tree_map(lambda s: NamedSharding(env.mesh, s),
                                  param_specs(params, env))


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------
def compat_shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the repo's default of no varying-axis check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ---------------------------------------------------------------------------
# Layout autotuner (Swallow §II-B: pick the balanced design point)
# ---------------------------------------------------------------------------
def autotune_layout(cfg, shape=None, n_chips: Optional[int] = None,
                    mode: str = "circuit", link=None,
                    max_model: Optional[int] = None,
                    serving: bool = False):
    """Pick the (data, model) mesh factorization the cost engine scores
    fastest for ``cfg`` at ``shape``.

    Returns ``(best, ranked)`` where ``best`` is a
    :class:`repro.core.costs.CostEstimate` (``best.layout`` is the chosen
    :class:`~repro.core.costs.Layout`) and ``ranked`` is every candidate,
    fastest first.  ``n_chips`` defaults to the visible device count.
    Pure host-side arithmetic except that default — no arrays are placed.

    ``serving=True`` prices the paged-KV stripe traffic on top of the
    transformer collectives (:func:`repro.core.costs.rank_serving_layouts`
    — the §V link model applied to the (n-1)/n remote fraction of KV
    writes plus the per-window decode stats merge).
    """
    from repro.core import costs as costs_mod
    if n_chips is None:
        n_chips = len(jax.devices())
    link = link or costs_mod.LinkSpec()
    rank = (costs_mod.rank_serving_layouts if serving
            else costs_mod.rank_layouts)
    ranked = rank(cfg, shape, n_chips, mode, link, max_model)
    return ranked[0], ranked


def make_layout_mesh(layout):
    """Realise a :class:`~repro.core.costs.Layout` as a jax Mesh
    (None for the trivial single-chip layout)."""
    from repro.launch.mesh import make_test_mesh
    if layout.n_chips == 1:
        return None
    return make_test_mesh(layout.data, layout.model, layout.pod)
