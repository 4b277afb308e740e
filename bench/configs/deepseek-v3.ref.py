"""Plain reference of DeepSeek-V3 on one chip's share, and its weights.

The forward pass of ``DeepseekV3ForCausalLM`` as published (its
``modeling_deepseek.py``, arXiv:2412.19437), for the layers, experts and
vocabulary slice the configuration file holds:

* token embedding; per layer RMSNorm -> multi-head latent attention ->
  residual; RMSNorm -> FFN -> residual; final RMSNorm and the output head;
* attention: ``q = q_b(rms(q_a(x)))`` split per head into ``q_nope``
  (qk_nope_head_dim) and ``q_rope`` (qk_rope_head_dim); ``kv_a(x)`` gives
  the latent ``c = rms(.)`` (kv_lora_rank) and one shared ``k_rope``;
  keys and values are up-projected, ``[k_nope, v] = kv_b(c)`` per head,
  the key is ``[k_nope, k_rope]``; causal softmax at the scale below;
  ``wo`` over the heads' values;
* rotary embedding YaRN (``DeepseekV3YarnRotaryEmbedding``): with
  ``d(r) = dim ln(L / (2 pi r)) / (2 ln theta)`` for L the original
  positions, ``low = max(floor(d(beta_fast)), 0)``, ``high =
  min(ceil(d(beta_slow)), dim - 1)``, ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``, the inverse frequencies ``inv_i = theta^(-2i/dim) (1 -
  ramp_i) + theta^(-2i/dim) / factor * ramp_i``; cos and sin times
  ``m(mscale) / m(mscale_all_dim)`` with ``m(s) = 0.1 s ln(factor) + 1``;
  the softmax scale ``(nope + rope)^-1/2 * m(mscale_all_dim)^2``;
* FFN: SwiGLU ``down(silu(gate(x)) * up(x))``; the first
  ``first_k_dense_replace`` layers dense, the others the shared expert
  plus the routed experts: ``noaux_tc`` routing (``MoEGate``) of sigmoid
  scores ``s`` over all ``deployment.routed_experts``: the choice
  scores ``s + bias``, groups scored by the sum of their two best, the
  ``topk_group`` best groups kept, the ``num_experts_per_tok`` best
  choices among them, weights ``s`` there normalised to sum 1 times
  ``routed_scaling_factor``.  Of those the experts this chip holds
  (``n_routed_experts`` from ``deployment.first_expert``) add their part;
  the others lie on other chips and are left out, as in the program.

Judged positions.  A choice between experts whose choice scores lie
closer than the rounding of a bfloat16 pass moves them is decided by
that rounding, and a flipped held expert moves the position's logits
as far as float8 weights do.  So a position is judged only where, in
every expert layer, the choice of held experts is the same for every
router output within ``TIE`` times the row's rms router output of the
reference's (:func:`_settled`, interval bounds through each step of the
rule); elsewhere its logits are all 0 and no served token there reads a
gap.  The float8 control is judged at the same positions.

Departures: rotary embedding pairs dimension i with i + dim/2 (NeoX
halves); the published code pairs interleaved dimensions, which on
random weights is the same up to a fixed permutation of the rope
columns of ``q_b`` and ``kv_a``.  The multi-token-prediction module is
left out.

It runs in float32 with every product at ``HIGHEST`` precision, imports
nothing of the program, and keeps the weights in bfloat16, widening one
layer's at a time (a float32 copy of the whole share is 12.6 GB).
Attention runs ``HEAD_GROUP`` heads at a time, each group's keys and
values up-projected whole, in blocks of ``BLOCK`` query rows against key
blocks up to the diagonal with an online softmax, so that no score
matrix of a long sequence exists whole.  ``quant="fp8"`` is the
control: the same pass with every weight matrix rounded to float8
(e4m3) with a scale per output channel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BLOCK = 1024
HEAD_GROUP = 16
# bfloat16 serving moves the router's outputs by 1.2-1.5% of their rms
# (at 1,024 wide, through five layers); at 5% no flipped held expert
# falls on a judged position, and about 44% of positions are judged
TIE = 0.05
MLA = ("q_a", "q_b", "kv_a", "kv_b", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERTS = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
NORMS = ("ln1", "ln2", "q_a_norm", "kv_a_norm")


def _sizes(cfg):
    H = cfg["num_attention_heads"]
    return dict(
        L=cfg["num_hidden_layers"], K=cfg["first_k_dense_replace"],
        d=cfg["hidden_size"], H=H, ql=cfg["q_lora_rank"],
        kl=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        held=cfg["n_routed_experts"], V=cfg["vocab_size"])


def make_weights(cfg: dict, seed: int) -> dict:
    """Random weights from ``seed``, made on the device in one jitted
    call with the chip's own bit generator, in the configuration's
    ``torch_dtype``, stacked over layers (attention over all layers, the
    dense FFN over the dense ones, experts over the others).  Matrices
    are (in, out), N(0, initializer_range**2); the router (float32, as
    the published gate computes) likewise; its correction bias
    N(0, router_bias_std**2) (``assumed``); norm scales 1 + 0.1 N(0, 1)
    so that a missing or misplaced norm shows.

    Beside them, as the published model keeps its rotary embedding's
    inverse frequencies as a buffer: ``rope_inv_freq``, the magnitude
    of cos and sin ``rope_mscale`` and the ``softmax_scale`` (YaRN, from
    ``rope_scaling``), and the global ids of the experts this chip holds
    (``held_experts``), the configuration's groups that the comparison
    does not hand :func:`hidden`."""
    dt = jnp.dtype(cfg["torch_dtype"])
    std = cfg["initializer_range"]
    z = _sizes(cfg)
    L, K, d, H = z["L"], z["K"], z["d"], z["H"]
    M = L - K
    shapes = {"embed": (z["V"], d), "head": (d, z["V"]),
              "q_a": (L, d, z["ql"]),
              "q_b": (L, z["ql"], H * (z["nope"] + z["rope"])),
              "kv_a": (L, d, z["kl"] + z["rope"]),
              "kv_b": (L, z["kl"], H * (z["nope"] + z["v"])),
              "wo": (L, H * z["v"], d),
              "w_gate": (K, d, z["f"]), "w_up": (K, d, z["f"]),
              "w_down": (K, z["f"], d),
              "e_gate": (M, z["held"], d, z["fe"]),
              "e_up": (M, z["held"], d, z["fe"]),
              "e_down": (M, z["held"], z["fe"], d),
              "s_gate": (M, d, z["fs"]), "s_up": (M, d, z["fs"]),
              "s_down": (M, z["fs"], d)}
    norms = {"ln1": (L, d), "ln2": (L, d), "q_a_norm": (L, z["ql"]),
             "kv_a_norm": (L, z["kl"]), "final_norm": (d,)}
    bias_std = cfg["router_bias_std"]
    E, first = (cfg["deployment"][k] for k in ("routed_experts",
                                               "first_expert"))
    inv, mag, scale = _yarn(cfg)

    def make(key):
        names = sorted(shapes) + sorted(norms) + ["router", "router_bias"]
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {n: (jax.random.normal(keys[n], s, jnp.float32) * std
                   ).astype(dt) for n, s in shapes.items()}
        out.update({n: (1.0 + 0.1 * jax.random.normal(keys[n], s,
                                                      jnp.float32)).astype(dt)
                    for n, s in norms.items()})
        out["router"] = jax.random.normal(keys["router"], (M, d, E),
                                          jnp.float32) * std
        out["router_bias"] = jax.random.normal(
            keys["router_bias"], (M, E), jnp.float32) * bias_std
        out.update(rope_inv_freq=inv,
                   rope_mscale=jnp.float32(mag),
                   softmax_scale=jnp.float32(scale),
                   held_experts=first + jnp.arange(z["held"]))
        return out

    key = jax.random.fold_in(jax.random.key(seed % 2 ** 31, impl="rbg"),
                             seed // 2 ** 31)
    return jax.jit(make)(key)


def _fp8(w, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (the
    contraction axis), back in float32."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _yarn(cfg):
    """(inverse frequencies (rope/2,), cos/sin magnitude, softmax scale)
    by the equations in the module's docstring."""
    r, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    theta, factor = float(cfg["rope_theta"]), float(r["factor"])

    def d(rot):
        return dim * math.log(r["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))

    def m(s):
        return 1.0 if factor <= 1 else 0.1 * s * math.log(factor) + 1.0
    low = max(math.floor(d(r["beta_fast"])), 0)
    high = min(math.ceil(d(r["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / (high - low if high > low else 0.001), 0, 1)
    base = 1.0 / theta ** (2 * i / dim)
    inv = base * (1 - ramp) + base / factor * ramp
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 \
        * m(r["mscale_all_dim"]) ** 2
    return inv, m(r["mscale"]) / m(r["mscale_all_dim"]), scale


def _rope(x, pos, inv, mag):
    """Rotate (S, ..., dim) by positions (S,), NeoX halves."""
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, half)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * mag).reshape(shape)
    sin = (jnp.sin(ang) * mag).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, scale, block: int = BLOCK):
    """Causal softmax attention of q, k (S, G, dq) and v (S, G, dv):
    ``block`` query rows at a time against the key blocks up to the
    diagonal, with an online softmax; returns (S, G, dv)."""
    S, G, dq = q.shape
    nb = -(-S // block)
    pad = ((0, nb * block - S), (0, 0), (0, 0))
    qb, kb, vb = (jnp.pad(a, pad).reshape(nb, block, G, a.shape[-1])
                  for a in (q, k, v))
    rows = jnp.arange(block)

    def row(i):
        def step(j, carry):
            mx, l, acc = carry
            s = jnp.einsum("qgd,kgd->gqk", qb[i], kb[j],
                           precision=HI) * scale
            s = jnp.where((i * block + rows)[:, None]
                          >= (j * block + rows)[None, :], s, -jnp.inf)
            mx_new = jnp.maximum(mx, s.max(-1))
            e = jnp.exp(s - mx_new[..., None])
            corr = jnp.exp(mx - mx_new)
            return (mx_new, l * corr + e.sum(-1), acc * corr[..., None]
                    + jnp.einsum("gqk,kgd->gqd", e, vb[j], precision=HI))
        init = (jnp.full((G, block), -jnp.inf), jnp.zeros((G, block)),
                jnp.zeros((G, block, v.shape[-1])))
        _, l, acc = jax.lax.fori_loop(0, i + 1, step, init)
        return jnp.moveaxis(acc / l[..., None], 0, 1)

    return jax.lax.map(row, jnp.arange(nb)).reshape(nb * block, G, -1)[:S]


def _attention(x, lw, widen, cfg, pos, yarn, block):
    """x plus the layer's attention output, ``HEAD_GROUP`` heads at a
    time: each group's queries, keys and values are made whole and
    attended, and its share of ``wo`` added."""
    z = _sizes(cfg)
    S, H, nope, rope, kl = x.shape[0], z["H"], z["nope"], z["rope"], z["kl"]
    G = math.gcd(H, HEAD_GROUP)
    eps = cfg["rms_norm_eps"]
    inv, mag, scale = yarn
    h = _rms(x, lw["ln1"], eps)
    cq = _rms(jnp.matmul(h, widen(lw["q_a"]), precision=HI),
              lw["q_a_norm"], eps)
    kv = jnp.matmul(h, widen(lw["kv_a"]), precision=HI)
    c = _rms(kv[:, :kl], lw["kv_a_norm"], eps)
    k_rope = _rope(kv[:, kl:], pos, inv, mag)                # (S, rope)
    groups = (
        widen(lw["q_b"]).reshape(-1, H // G, G, nope + rope),
        widen(lw["kv_b"]).reshape(kl, H // G, G, nope + z["v"]),
        widen(lw["wo"]).reshape(H // G, G * z["v"], -1))

    def group(out, args):
        q_b, kv_b, wo = args
        q = jnp.einsum("sl,lgd->sgd", cq, q_b, precision=HI)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv,
                                                  mag)], -1)
        up = jnp.einsum("sl,lgd->sgd", c, kv_b, precision=HI)
        k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
            k_rope[:, None], (S, G, rope))], -1)
        o = attend(q, k, up[..., nope:], scale, block).reshape(S, -1)
        return out + jnp.matmul(o, wo, precision=HI), None
    out, _ = jax.lax.scan(group, x, (jnp.moveaxis(groups[0], 1, 0),
                                     jnp.moveaxis(groups[1], 1, 0),
                                     groups[2]))
    return out


def _swiglu(h, gate, up, down, rows: int = 2048):
    """``down(silu(gate(h)) * up(h))``, ``rows`` rows of h at a time (a
    32k-token sequence's dense FFN is 2.4 GB a matrix in float32)."""
    S = h.shape[0]
    nb = -(-S // rows)
    hb = jnp.pad(h, ((0, nb * rows - S), (0, 0))).reshape(nb, rows, -1)

    def one(hi):
        g = jnp.matmul(hi, gate, precision=HI)
        u = jnp.matmul(hi, up, precision=HI)
        return jnp.matmul(jax.nn.silu(g) * u, down, precision=HI)
    return jax.lax.map(one, hb).reshape(nb * rows, -1)[:S]


def _route(h, router, bias, held, cfg):
    """Each token's weight on each of the ``held`` experts (S, held) by
    the ``noaux_tc`` rule over all routed experts (the router's width),
    and whether the rule's choice of held experts is settled (S,): see
    :func:`_settled`."""
    E, ng, k = router.shape[-1], cfg["n_group"], cfg["num_experts_per_tok"]
    S = h.shape[0]
    x = jnp.matmul(h, router, precision=HI)                  # (S, E)
    s = jax.nn.sigmoid(x)
    c = s + bias[None]
    best2 = jax.lax.top_k(c.reshape(S, ng, E // ng), 2)[0].sum(-1)
    keep = jax.lax.top_k(best2, cfg["topk_group"])[1]        # (S, tg)
    kept = (jnp.arange(ng)[None, :, None] == keep[:, None, :]).any(-1)
    c = jnp.where(jnp.repeat(kept, E // ng, axis=1), c, -jnp.inf)
    ids = jax.lax.top_k(c, k)[1]
    w = jnp.take_along_axis(s, ids, -1)
    if cfg["norm_topk_prob"] and k > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    share = jnp.einsum("sk,ske->se", w,
                       (ids[..., None] == held).astype(jnp.float32))
    return share, _settled(x, bias, held, cfg)


def _kth_other(a, k: int):
    """For each column j of ``a`` (S, E), the k-th largest of the row
    without a[:, j] (a column among the row's k largest makes way for
    the (k+1)-th)."""
    top = jax.lax.top_k(a, k + 1)[0]
    return jnp.where(a >= top[:, k - 1:k], top[:, k:k + 1], top[:, k - 1:k])


def _settled(x, bias, held, cfg):
    """Whether the rule's choice of held experts holds for every router
    output within ``TIE`` times the row's rms router output of ``x``
    (S, E): the same interval bounds through every step of the
    rule.  Each choice score lies in [lo, hi]; a group's score (sum of
    its two best) in [G_lo, G_hi]; a group is surely kept where its G_lo
    beats the ``topk_group``-th best G_hi of the others, surely dropped
    where its G_hi falls below the ``topk_group``-th best G_lo of the
    others; a held expert is surely chosen where its group is surely
    kept and its lo beats the ``top_k``-th best hi of the others in the
    groups that may be kept, surely not where its group is surely
    dropped or its hi falls below the ``top_k``-th best lo of the others
    in the groups surely kept.  A row is settled where each held expert
    is one or the other."""
    S, E = x.shape
    ng, tg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    d = TIE * jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    lo = jax.nn.sigmoid(x - d) + bias[None]
    hi = jax.nn.sigmoid(x + d) + bias[None]
    g_lo = jax.lax.top_k(lo.reshape(S, ng, E // ng), 2)[0].sum(-1)
    g_hi = jax.lax.top_k(hi.reshape(S, ng, E // ng), 2)[0].sum(-1)
    g_in = g_lo > _kth_other(g_hi, tg)                       # (S, ng)
    g_out = g_hi < _kth_other(g_lo, tg)
    e_in = jnp.repeat(g_in, E // ng, axis=1)                 # (S, E)
    e_out = jnp.repeat(g_out, E // ng, axis=1)
    hi_may = jnp.where(e_out, -jnp.inf, hi)
    lo_sure = jnp.where(e_in, lo, -jnp.inf)
    chosen = e_in & (lo > _kth_other(hi_may, k))
    dropped = e_out | (hi < _kth_other(lo_sure, k))
    return (chosen | dropped)[:, held].all(-1)


def hidden(weights: dict, cfg: dict, tokens, quant: str = "none",
           block: int = BLOCK):
    """Final-normed hidden states of one causal sequence and whether each
    position is judged (:class:`Hidden`).  A position is judged where
    every expert layer's choice of held experts is settled
    (:func:`_settled`); the float8 pass judges every position."""
    if quant not in ("none", "fp8"):
        raise ValueError(quant)
    K, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    eps = cfg["rms_norm_eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    yarn = (weights["rope_inv_freq"], weights["rope_mscale"],
            weights["softmax_scale"])
    widen = (lambda w: _fp8(w.astype(jnp.float32), -2)) if quant == "fp8" \
        else (lambda w: w.astype(jnp.float32))
    emb = weights["embed"]
    x = (_fp8(emb.astype(jnp.float32), -1) if quant == "fp8"
         else emb.astype(jnp.float32))[tokens]

    def at(names, i):
        """Layer ``i`` (traced) of the stacks ``names``: one layer's
        weights cut out at a time."""
        return {n: jax.lax.dynamic_index_in_dim(weights[n], i, 0, False)
                for n in names}

    def dense_layer(x, i):
        lw = at(MLA + NORMS + DENSE, i)
        x = _attention(x, lw, widen, cfg, pos, yarn, block)
        return x + _swiglu(_rms(x, lw["ln2"], eps),
                           *(widen(lw[n]) for n in DENSE)), None

    def expert_layer(x, i):
        lw = at(MLA + NORMS, i)
        lw.update(at(EXPERTS + ("router", "router_bias"), i - K))
        x = _attention(x, lw, widen, cfg, pos, yarn, block)
        h = _rms(x, lw["ln2"], eps)
        share, settled = _route(h, lw["router"], lw["router_bias"],
                                weights["held_experts"], cfg)

        def one(acc, xs):
            gate, up, down, weight = xs
            return acc + weight[:, None] * _swiglu(
                h, widen(gate), widen(up), widen(down)), None
        routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
            lw["e_gate"], lw["e_up"], lw["e_down"], share.T))
        return x + routed + _swiglu(h, *(widen(lw[n]) for n in
                                         ("s_gate", "s_up", "s_down"))), \
            settled

    x, _ = jax.lax.scan(dense_layer, x, jnp.arange(K))
    x, settled = jax.lax.scan(expert_layer, x, jnp.arange(K, L))
    judged = jnp.ones((S,), bool) if quant == "fp8" else settled.all(0)
    return Hidden(_rms(x, weights["final_norm"], eps), judged)


class Hidden:
    """What :func:`hidden` returns: the final-normed states (S, d) and
    the judged flags (S,).  Indexed by positions it gives the rows
    :func:`logits` takes, (n, d + 1) with the flag last."""

    def __init__(self, states, judged):
        self.states, self.judged = states, judged

    def __getitem__(self, idx):
        return jnp.concatenate([self.states[idx], self.judged[idx][:, None]
                                .astype(jnp.float32)], -1)


def logits(weights: dict, cfg: dict, h, quant: str = "none"):
    """Output-head logits (n, V) in float32 of rows of :class:`Hidden`;
    all 0 in a row that is not judged, so that no token there reads a
    gap."""
    w = weights["head"].astype(jnp.float32)
    w = _fp8(w, -2) if quant == "fp8" else w
    lg = jnp.matmul(h[:, :-1], w, precision=HI)
    return jnp.where(h[:, -1:] > 0, lg, 0.0)
