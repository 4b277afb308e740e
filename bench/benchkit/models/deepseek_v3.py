"""DeepSeek-V3 (``model_type`` ``deepseek_v3``): the program's
``ModelConfig``, its parameter tree, and the work one token needs.

Every layer attends through multi-head latent attention (MLA); the first
``first_k_dense_replace`` layers have a dense FFN, the others routed
experts beside a shared one.  The configuration file holds one chip's
share of a stated deployment (its ``deployment`` group): the router keeps
its published width (``deployment.routed_experts``) and this chip holds
``n_routed_experts`` of them from ``deployment.first_expert``, and the
vocabulary is the chip's slice (``vocab_size``).

The work count: every matrix outside the routed experts is read once a
decode step; a held expert only where some token of the step routes to
it (:meth:`Work.expert_read_share`); a token's routed-expert work is the
expected share ``top_k / routed_experts`` of it per held expert.
Attention costs per key and layer, with C = kv_lora + rope, absorbed
(in latent space) ``2 H (C + kv_lora)`` per query; the up-projected
path costs ``2 H (nope + rope + v)`` per query plus ``2 kv_lora H (nope
+ v)`` per key to up-project it through ``kv_b``.  A decode token
attends absorbed; a prompt is counted in the slices the engine cuts it
into (the configuration's ``engine`` settings), each on the path the
program takes at its width (``mla.absorbed_cheaper``).
"""
from __future__ import annotations

from dataclasses import dataclass


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file.  The
    multi-token-prediction module is left out (``mtp_depth`` 0); the
    program normalises the routing weights (``norm_topk_prob``)."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    RopeScaling)
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program normalises the routing weights")
    dep, rs = cfg["deployment"], cfg["rope_scaling"]
    dt = cfg["torch_dtype"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], layer_pattern=("mla",),
        first_k_dense=cfg["first_k_dense_replace"],
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"],
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(n_experts=dep["routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      score_func=cfg["scoring_func"],
                      topk_method=cfg["topk_method"],
                      n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                      routed_scaling_factor=float(
                          cfg["routed_scaling_factor"]),
                      n_held=cfg["n_routed_experts"],
                      first_held=dep["first_expert"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], act=cfg["hidden_act"],
        gated_ffn=True, mtp_depth=0, param_dtype=dt, activation_dtype=dt)


def program_params(weights: dict, mcfg):
    """The reference's weights (``deepseek-v3.ref.py``) in the program's
    tree: the dense layers as one segment, the expert layers as a
    scanned one (matrices shared, not copied; norm scales in float32 as
    the program keeps them)."""
    import jax
    import jax.numpy as jnp
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    k = mcfg.first_k_dense

    def block(at, ffn):
        """Layer ``at`` (an index) or the stack of layers ``at`` (a
        slice)."""
        mla = {n: weights[n][at] for n in ("q_a", "q_b", "kv_a", "kv_b",
                                           "wo")}
        mla.update(q_a_norm=f32(weights["q_a_norm"][at]),
                   kv_a_norm=f32(weights["kv_a_norm"][at]))
        return {"ln1": {"scale": f32(weights["ln1"][at])}, "mla": mla,
                "ln2": {"scale": f32(weights["ln2"][at])}, **ffn}

    dense = [block(i, {"ffn": {n: weights[n][i]
                               for n in ("w_gate", "w_up", "w_down")}})
             for i in range(k)]
    moe = {"moe": {"router_w": weights["router"],
                   "router_bias": weights["router_bias"],
                   "e_gate": weights["e_gate"], "e_up": weights["e_up"],
                   "e_down": weights["e_down"]},
           "shared": {"w_gate": weights["s_gate"], "w_up": weights["s_up"],
                      "w_down": weights["s_down"]}}
    # one segment per run of layers, scanned (stacked) where it holds
    # more than one (lm.make_segments)
    segments = []
    if k == 1:
        segments.append(dense)
    elif k > 1:
        segments.append([jax.tree.map(lambda *a: jnp.stack(a), *dense)])
    if mcfg.n_layers - k == 1:
        segments.append([block(k, jax.tree.map(lambda a: a[0], moe))])
    elif mcfg.n_layers > k:
        segments.append([block(slice(k, None), moe)])
    return {"embed": {"embed_table": weights["embed"]},
            "segments": segments,
            "final_norm": {"scale": f32(weights["final_norm"])},
            "head": {"head_w": weights["head"]}}


def work(cfg: dict) -> "Work":
    return Work.of(cfg)


@dataclass(frozen=True)
class Work:
    """The sizes that set DeepSeek-V3's work on one chip's share."""
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    ffn: int
    expert_ffn: int
    shared: int
    routed: int                 # experts the router scores
    held: int                   # experts this chip holds
    top_k: int
    vocab: int
    chunk_tokens: int           # the engine's prefill slices (0: whole)
    page_size: int
    bytes_per_param: int = 2
    router_bytes: int = 4       # the router is kept in float32

    @classmethod
    def of(cls, cfg: dict) -> "Work":
        return cls(layers=cfg["num_hidden_layers"],
                   dense_layers=cfg["first_k_dense_replace"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
                   nope=cfg["qk_nope_head_dim"],
                   rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
                   ffn=cfg["intermediate_size"],
                   expert_ffn=cfg["moe_intermediate_size"],
                   shared=cfg["n_shared_experts"],
                   routed=cfg["deployment"]["routed_experts"],
                   held=cfg["n_routed_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   vocab=cfg["vocab_size"],
                   chunk_tokens=(cfg["engine"]["chunk_tokens"]
                                 if cfg["engine"]["chunked_prefill"] else 0),
                   page_size=cfg["engine"]["page_size"],
                   bytes_per_param=_itemsize(cfg["torch_dtype"]))

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def kv_b_params(self) -> int:
        """``kv_b``: the latent up-projection to keys (nope) and values."""
        return self.kv_lora * self.heads * (self.nope + self.v)

    @property
    def mla_params(self) -> int:
        """One layer's latent attention, ``kv_b`` included."""
        d, H = self.d_model, self.heads
        return (d * self.q_lora + self.q_lora * H * (self.nope + self.rope)
                + d * (self.kv_lora + self.rope) + self.kv_b_params
                + H * self.v * d)

    @property
    def expert_params(self) -> int:
        """One expert (gated: gate, up and down)."""
        return 3 * self.d_model * self.expert_ffn

    @property
    def router_params(self) -> int:
        return self.d_model * self.routed

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def weight_bytes(self) -> int:
        """Bytes a decode step reads once whatever its batch: every
        layer's latent attention, the dense FFNs, the shared experts,
        the routers (float32) and the output head; not the held routed
        experts (:attr:`expert_bytes`)."""
        b = self.bytes_per_param
        return (b * (self.layers * self.mla_params
                     + self.dense_layers * 3 * self.d_model * self.ffn
                     + self.moe_layers * self.shared * self.expert_params
                     + self.head_params)
                + self.router_bytes * self.moe_layers * self.router_params)

    @property
    def expert_bytes(self) -> int:
        """Bytes of every held expert of every expert layer."""
        return self.bytes_per_param * self.moe_layers * self.held \
            * self.expert_params

    def expert_read_share(self, tokens: float) -> float:
        """Expected share of the held experts a step of ``tokens`` tokens
        reads: those at least one token routes to, each token choosing
        ``top_k`` of the ``routed`` experts."""
        return 1.0 - (1.0 - self.top_k / self.routed) ** tokens

    @property
    def kv_bytes_per_token(self) -> int:
        """One latent row, ``[c_kv, k_rope]``, per layer."""
        return self.layers * (self.kv_lora + self.rope) * self.bytes_per_param

    @property
    def absorbed_per_key(self) -> int:
        return 2 * self.heads * (2 * self.kv_lora + self.rope)

    @property
    def upprojected_per_key(self) -> int:
        return 2 * self.heads * (self.nope + self.rope + self.v)

    def _ffn_flops(self) -> float:
        """Operations of one token through every layer's FFN: the dense
        ones, the shared experts, the routers and the held experts in
        expectation (``top_k / routed`` of a token per held expert)."""
        d = self.d_model
        return (self.dense_layers * 2 * 3 * d * self.ffn
                + self.moe_layers * (
                    2 * self.shared * self.expert_params
                    + 2 * self.router_params
                    + 2 * self.expert_params * self.held * self.top_k
                    / self.routed))

    def token_flops(self, context: int) -> float:
        """Operations of one decode token through every layer, attending
        absorbed to ``context`` keys (itself included), without the
        output head."""
        return (self.layers * (2 * self.mla_params
                               + self.absorbed_per_key * context)
                + self._ffn_flops())

    @property
    def head_flops(self) -> int:
        return 2 * self.head_params

    def prefill_flops(self, start: int, n: int) -> float:
        """Operations of prompt positions ``start .. start + n - 1``
        (position p attends to p + 1 keys), without the head: the slices
        the engine cuts them into (up to ``chunk_tokens``, each but the
        last ending on a page boundary, as ``Scheduler._chunk_end``
        does), each on its path."""
        total, end = 0.0, start + n
        while start < end:
            stop = min(end, start + self.chunk_tokens) \
                if self.chunk_tokens else end
            if stop < end and stop - stop % self.page_size > start:
                stop -= stop % self.page_size
            total += self.slice_flops(start, stop - start)
            start = stop
        return total

    def absorbed(self, width: int) -> bool:
        """The program's choice for a slice compiled ``width`` queries
        wide (``mla.absorbed_cheaper``): absorbed where its operations
        per key are no more than the up-projected path's."""
        return width * self.absorbed_per_key <= (
            width * self.upprojected_per_key + 2 * self.kv_b_params)

    def slice_flops(self, start: int, n: int) -> float:
        """One slice of ``n`` prompt positions from ``start``, padded to
        the next power of two as the engine compiles it, on the path the
        program takes at that width; the padding is not counted."""
        pairs = n * start + n * (n + 1) // 2
        if self.absorbed(1 << max(n - 1, 0).bit_length()):
            attn = n * 2 * self.mla_params + pairs * self.absorbed_per_key
        else:
            attn = (n * 2 * (self.mla_params - self.kv_b_params)
                    + (start + n) * 2 * self.kv_b_params
                    + pairs * self.upprojected_per_key)
        return self.layers * attn + n * self._ffn_flops()


def _itemsize(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]
