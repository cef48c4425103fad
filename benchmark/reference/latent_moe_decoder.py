"""The plain reference of the latent-attention block: multi-head latent
attention (MLA) layers of two kinds, the full kind choosing its keys with a
learned indexer, the sliding kind seeing a window, every mixer gated a head,
a leading dense layer and sparse experts after it, in `jax.numpy`.

Written from the published configuration of dots3-note-prev (`config.json`:
`layer_types`, `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `index_n_heads`, `index_head_dim`,
`index_topk`, the `swa_*` keys, `sliding_window_size`,
`attention_gate_type`, `apply_mla_qkv_lora_rescale`, `first_k_dense_replace`,
`n_routed_experts`, `scoring_func`, ...), of multi-head latent attention
(DeepSeek-V2) and of the lightning indexer (DeepSeek-V3.2-Exp), whose key
names the config uses. float32 throughout, every matrix multiplication at
`highest` precision. Attention is the PROJECTED form (keys and values of
every head made from the latent, no absorption) under an explicit
visibility mask; the mask of a full layer is built from dense index scores
and an exact top-k; the experts run one at a time under `lax.scan` over the
held ones; there is no cache. Queries run in blocks only so that the
[heads, block, S] scores of a 16k row fit beside a layer's weights. It
imports nothing from `llama_pipeline_parallel_tpu`.

Pre-norm residual block, RMSNorm: `h += mixer(norm(h)); h += ffn(norm(h))`,
`x = norm(h)`, `rope(.)` the rotate-half rotary embedding at the layer
kind's theta on the rope part of a head.

Mixer (full: H 128, latents 1024 / 512, nope 128 + rope 64, v 128; sliding:
H 64, latents 1024 / 1024, nope 192 + rope 64, v 128):
    cq = r_q rmsnorm(W_qa x);  [q^N_h; q^R_h] = W_qb,h cq,  q^R roped
    [c; k^R] = W_kva x;  c = r_kv rmsnorm(c),  k^R roped, shared by the heads
    k_h,s = [W_kb,h^K c_s; k^R_s],  v_h,s = W_kb,h^V c_s
    o_h,t = sum_{s in S_t} softmax_s(q_h,t . k_h,s / sqrt(nope + rope)) v_h,s
    y_t = W_o [sigmoid(W_g x_t)_h o_h,t]_h
Indexer of a full layer (64 heads of 128, rope on the first 64 numbers):
    qI_t,j = W_qI,j cq_t;  kI_s = layernorm(W_kI x_s);  w_t = W_w x_t / sqrt(64 * 128)
    I_t,s = sum_j w_t,j relu(qI_t,j . kI_s)   for s <= t
    S_t = {t} and the largest I_t,s until there are `index_topk` (every
          s <= t where there are no more), ties to the lower position
Sliding layer: S_t = {s : t - window < s <= t}.
Feed-forward: layer 0 a SwiGLU of width `intermediate_size`; later layers
`s = sigmoid(W_r x)` over the router's width, the k largest of `s + bias`,
a selected expert's weight `s_e / sum of the selected s` times the scaling
factor, `y = sum_selected w_e SwiGLU_e(x) + SwiGLU_shared(x)`.

Departures from the published description, each forced by what
`config.json` leaves out (the configuration file lists them under
`assumed`):
- `apply_mla_qkv_lora_rescale` is read as r_q = sqrt(hidden / q_lora_rank),
  r_kv = sqrt(hidden / kv_lora_rank) on the normed latents;
- the gate is one number a head (`headwise`), from the layer's normed
  input, applied before the output projection;
- the window counts the query's own position (s > t - window);
- rotate-half rope (a fixed permutation of the published interleaving);
- `kI`'s norm is a LayerNorm with bias; the indexer's Hadamard rotation is
  left out (orthogonal: it changes no qI . kI);
- the query's own position is always selected: it takes one of the
  `index_topk` places whatever its index score;
- the router's bias is zero under seeded weights; no groups; no MTP module,
  no vision or audio tower;
- the layer is told which experts it holds (`expert_offset`,
  `n_routed_experts`): it routes over all of `router_experts`, adds the
  terms of the held ones and leaves the others out.

`precision="fp8"` is the CONTROL (see `dense_decoder`): every weight
multiplication but the router's and the indexer's as a float8 recipe
computes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import HIGHEST, _mm, rms_norm, rotary

QUERY_BLOCK = 128          # queries attended at a time
LN_EPS = 1e-6
PERIOD = ("full", "sliding", "sliding", "sliding")


def dims(model: dict) -> dict:
    """The numbers of a configuration file the block needs, under short
    names, as a flat dict of hashable values."""
    layers = model["num_hidden_layers"]
    kinds = [t.split("_")[0] for t in model["layer_types"][:layers]]
    n = len(PERIOD)
    want = ["full"] + list(PERIOD) * ((layers - 1) // n)
    if (layers - 1) % n or kinds != want:
        raise ValueError(
            f"layer_types[:{layers}] must be one full layer, then whole "
            f"periods of {PERIOD}; got {kinds}")
    if model["first_k_dense_replace"] != 1:
        raise ValueError("this block has exactly one leading dense layer")
    if model["attention_gate_type"] != "headwise" or \
            model["swa_attention_gate_type"] != "headwise":
        raise ValueError("the gates are one number a head")
    if model["scoring_func"] != "sigmoid":
        raise ValueError("the router scores with a sigmoid")
    d = model["hidden_size"]
    rescale = bool(model["apply_mla_qkv_lora_rescale"])
    ratio = lambda rank: (d / rank) ** 0.5 if rescale else 1.0
    return {
        "d": d, "layers": layers, "vocab": model["vocab_size"],
        "eps": model["rms_norm_eps"],
        # the full kind
        "f_heads": model["num_attention_heads"],
        "f_rq": model["q_lora_rank"], "f_rkv": model["kv_lora_rank"],
        "f_nope": model["qk_nope_head_dim"], "f_rope": model["qk_rope_head_dim"],
        "f_v": model["v_head_dim"], "f_theta": float(model["rope_theta"]),
        "f_rq_scale": ratio(model["q_lora_rank"]),
        "f_rkv_scale": ratio(model["kv_lora_rank"]),
        "i_heads": model["index_n_heads"], "i_hd": model["index_head_dim"],
        "topk": model["index_topk"],
        # the sliding kind
        "s_heads": model["swa_num_attention_heads"],
        "s_rq": model["swa_q_lora_rank"], "s_rkv": model["swa_kv_lora_rank"],
        "s_nope": model["swa_qk_nope_head_dim"],
        "s_rope": model["swa_qk_rope_head_dim"],
        "s_v": model["swa_v_head_dim"],
        "s_theta": float(model["swa_rope_theta"]),
        "s_rq_scale": ratio(model["swa_q_lora_rank"]),
        "s_rkv_scale": ratio(model["swa_kv_lora_rank"]),
        "window": model["sliding_window_size"],
        # feed-forward
        "ffn": model["intermediate_size"],
        "router": model.get("router_experts", model["n_routed_experts"]),
        "held": model["n_routed_experts"],
        "offset": model.get("expert_offset", 0),
        "topk_experts": model["num_experts_per_tok"],
        "f": model["moe_intermediate_size"],
        "fs": model["n_shared_experts"] * model["moe_intermediate_size"],
        "norm_topk": bool(model["norm_topk_prob"]),
        "scale": float(model["routed_scaling_factor"]),
    }


def kind_of(index: int) -> str:
    """`full` or `sliding`: layer 0 is full, then the period repeats."""
    return "full" if index == 0 else PERIOD[(index - 1) % len(PERIOD)]


def kind_dims(dm: dict, kind: str) -> dict:
    p = "f_" if kind == "full" else "s_"
    return {k[2:]: v for k, v in dm.items() if k.startswith(p)}


def layer_norm(x, weight, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * weight + bias


def _rope_front(x, positions, n: int, theta: float):
    """Rotary embedding on the first `n` numbers of the last axis of
    x [b, s, h, hd]; the rest pass."""
    return jnp.concatenate(
        [rotary(x[..., :n], positions, theta), x[..., n:]], axis=-1)


def index_scores(mixer, x, cq, positions, dm: dict):
    """I [b, t, s] float32 for every pair, unmasked. The indexer's products
    are float32 at `highest` in every `precision`."""
    b, s, _ = x.shape
    nh, hd, rope = dm["i_heads"], dm["i_hd"], dm["f_rope"]
    mm = lambda a, w: jnp.matmul(a, w, precision=HIGHEST)
    qi = _rope_front(mm(cq, mixer["wqi"]).reshape(b, s, nh, hd), positions,
                     rope, dm["f_theta"])
    ki = layer_norm(mm(x, mixer["wki"]), mixer["ki_norm"], mixer["ki_bias"])
    ki = _rope_front(ki[:, :, None, :], positions, rope, dm["f_theta"])[:, :, 0]
    w = mm(x, mixer["ww"]) * (nh ** -0.5) * (hd ** -0.5)     # [b, s, nh]

    def block(args):
        q_blk, w_blk = args                                   # [b, B, nh, hd]
        dots = jnp.einsum("bthd,bsd->bths", q_blk, ki, precision=HIGHEST)
        return jnp.einsum("bths,bth->bts", jax.nn.relu(dots), w_blk,
                          precision=HIGHEST)

    return _by_query_blocks(block, (qi, w), s)


def _by_query_blocks(fn, arrays, s: int):
    """`fn` over blocks of QUERY_BLOCK positions of axis 1 of each array;
    the results concatenated on axis 1 and cut back to `s`."""
    pad = -s % QUERY_BLOCK
    n = (s + pad) // QUERY_BLOCK
    split = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            a.shape[0], n, QUERY_BLOCK, *a.shape[2:]), 1, 0)
    out = jax.lax.map(fn, tuple(split(a) for a in arrays))    # [n, b, B, ...]
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], n * QUERY_BLOCK, *out.shape[3:])[:, :s]


def select(scores, topk: int):
    """The visibility mask [b, t, s] of a full layer from its index scores:
    causal, the query's own position first, then the largest scores until
    there are `topk`, ties to the lower position (`lax.top_k`'s order)."""
    s = scores.shape[-1]
    t_idx, s_idx = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    ranked = jnp.where(s_idx < t_idx, scores, -jnp.inf)
    ranked = jnp.where(s_idx == t_idx, jnp.inf, ranked)
    if s <= topk:
        return ranked > -jnp.inf
    values, chosen = jax.lax.top_k(ranked, topk)
    b_idx = jnp.arange(scores.shape[0])[:, None, None]
    return jnp.zeros(scores.shape, bool).at[
        b_idx, t_idx[None, :, :], chosen].max(values > -jnp.inf)


def window_mask(s: int, window: int):
    t_idx, s_idx = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    return ((s_idx <= t_idx) & (s_idx > t_idx - window))[None]


def mla_mixer(mixer, x, positions, dm: dict, kind: str, precision: str,
              alter: tuple = ()):
    """One mixer's output [b, s, d] and, for a full layer, the visibility
    mask it attended under [b, t, s] (None for a sliding layer). `alter`
    names departures a test makes on purpose (tests only)."""
    kd = kind_dims(dm, kind)
    b, s, _ = x.shape
    H, nope, rope, v_dim = kd["heads"], kd["nope"], kd["rope"], kd["v"]
    cq = kd["rq_scale"] * rms_norm(_mm(x, mixer["wqa"], precision),
                                   mixer["q_norm"], dm["eps"])
    q = _mm(cq, mixer["wqb"], precision).reshape(b, s, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], positions, kd["theta"])], axis=-1)
    ckv = _mm(x, mixer["wkva"], precision)
    c = kd["rkv_scale"] * rms_norm(ckv[..., :kd["rkv"]], mixer["kv_norm"],
                                   dm["eps"])
    k_rope = rotary(ckv[..., None, kd["rkv"]:], positions, kd["theta"])
    k_nope = _mm(c, mixer["wkb_k"].reshape(kd["rkv"], H * nope),
                 precision).reshape(b, s, H, nope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, H, rope))], axis=-1)
    v = _mm(c, mixer["wkb_v"].reshape(kd["rkv"], H * v_dim),
            precision).reshape(b, s, H, v_dim)
    if kind == "full":
        scores = index_scores(mixer, x, cq, positions, dm)
        if "most_recent" in alter:      # the wrong selection, on purpose
            scores = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32),
                                      scores.shape)
        mask = select(scores, dm["topk"])
    else:
        mask = jnp.broadcast_to(window_mask(s, dm["window"]), (b, s, s))

    def block(args):
        q_blk, m_blk = args                       # [b, B, H, hd], [b, B, s]
        dots = jnp.einsum("bthd,bshd->bhts", q_blk, k, precision=HIGHEST)
        dots = jnp.where(m_blk[:, None], dots * (nope + rope) ** -0.5, -jnp.inf)
        # a block's padding rows see nothing: keep them finite
        dots = jnp.where(jnp.any(m_blk, -1)[:, None, :, None], dots, 0.0)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(dots, axis=-1), v,
                          precision=HIGHEST)

    out = _by_query_blocks(block, (q, mask), s)               # [b, s, H, v]
    gate = jax.nn.sigmoid(_mm(x, mixer["wg"], precision))     # [b, s, H]
    if "no_gate" in alter:
        gate = jnp.ones_like(gate)
    out = (out * gate[..., None]).reshape(b, s, H * v_dim)
    return _mm(out, mixer["wo"], precision), (mask if kind == "full" else None)


def _swiglu(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision)) * _mm(h, up, precision),
               down, precision)


def route(moe, h, dm: dict):
    """[b, s, d] -> combine weights [b, s, router]: a selected expert's weight
    at its place, 0 elsewhere."""
    scores = jax.nn.sigmoid(jnp.matmul(h, moe["router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + moe["router_bias"], dm["topk_experts"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if dm["norm_topk"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * dm["scale"]
    onehot = jax.nn.one_hot(chosen, dm["router"], dtype=jnp.float32)
    return jnp.einsum("bsk,bske->bse", picked, onehot, precision=HIGHEST)


def moe_layer(moe, h, dm: dict, precision: str, shared: bool = True):
    """The held experts' terms plus the shared expert's (`shared=False`
    leaves it out: the shares of several chips count it once)."""
    combine = route(moe, h, dm)
    held = jax.lax.dynamic_slice_in_dim(combine, dm["offset"], dm["held"], 2)

    def one_expert(total, xs):
        gate, up, down, weight = xs
        return total + weight[..., None] * _swiglu(h, gate, up, down,
                                                   precision), None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (moe["gate"], moe["up"], moe["down"], jnp.moveaxis(held, 2, 0)))
    if shared:
        total = total + _swiglu(h, moe["shared_gate"], moe["shared_up"],
                                moe["shared_down"], precision)
    return total


def block(layer, x, positions, dm: dict, kind: str,
          precision: str = "float32", alter: tuple = ()):
    """One layer: (x, the full layer's visibility mask or None). The layer
    is dense when it has `mlp`, sparse when it has `moe`."""
    h = rms_norm(x, layer["input_norm"], dm["eps"])
    mixed, mask = mla_mixer(layer["mixer"], h, positions, dm, kind, precision,
                            alter)
    x = x + mixed
    h = rms_norm(x, layer["post_norm"], dm["eps"])
    if "mlp" in layer:
        m = layer["mlp"]
        return x + _swiglu(h, m["gate"], m["up"], m["down"], precision), mask
    return x + moe_layer(layer["moe"], h, dm, precision), mask


@functools.partial(jax.jit, static_argnames=("dm_items", "kind", "precision",
                                             "alter"))
def _block_jit(layer, x, positions, rows, *, dm_items, kind, precision, alter):
    x, mask = block(layer, x, positions, dict(dm_items), kind, precision,
                    alter)
    if mask is None:
        return x, None
    # only the rows asked for leave the program: [b, n, s]
    return x, jnp.take_along_axis(mask, rows[..., None], axis=1)


def _freeze(dm: dict) -> tuple:
    return tuple(sorted(dm.items()))


def forward(top: dict, layer_fn, ids, model: dict, precision: str = "float32",
            rows=None, alter: tuple = ()):
    """[b, s] token ids -> (logits [b, s, vocab], selections). `top` holds
    `embed`, `norm` and `lm_head`; `layer_fn(i)` gives layer `i`'s weights in
    float32, one layer at a time (the layer is dropped before the next is
    made). `rows` [b, n] query positions: `selections` is then the
    visibility mask of those queries in every full layer, bool
    [full layers, b, n, s]; None without `rows`. Requests run one at a time
    inside a layer, so a layer's weights are made once for all of them."""
    dm = dims(model)
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (1, s))
    asked = jnp.zeros((b, 1), jnp.int32) if rows is None else jnp.asarray(rows)
    xs = [top["embed"][ids[i:i + 1]] for i in range(b)]
    masks = []
    for i in range(dm["layers"]):
        layer, kind, per_row = layer_fn(i), kind_of(i), []
        for r in range(b):
            xs[r], mask = _block_jit(
                layer, xs[r], positions, asked[r:r + 1], dm_items=_freeze(dm),
                kind=kind, precision=precision, alter=tuple(alter))
            per_row.append(mask)
        del layer
        if kind == "full":
            masks.append(jnp.concatenate(per_row, axis=0))
    x = rms_norm(jnp.concatenate(xs, axis=0), top["norm"], dm["eps"])
    logits = _mm(x, top["lm_head"], precision)
    return logits, (jnp.stack(masks) if rows is not None else None)


def logits_fn(top: dict, layer_fn, ids, model: dict,
              precision: str = "float32", alter: tuple = ()):
    return forward(top, layer_fn, ids, model, precision, alter=alter)[0]


def served_token_gaps(top: dict, layer_fn, prompts: list, served: list,
                      model: dict, pad_to: int, precision: str = "float32",
                      rows=None, alter: tuple = ()):
    """As `hybrid_moe_decoder.served_token_gaps`: prompt + served tokens
    padded at the END to `pad_to`, which no causal mask looks at. Per
    request, for each served token, the float32 reference's best logit minus
    its logit of the served token (under a lower `precision`: of the token
    that precision puts first). With `rows` ([b, n] query positions) also
    returns the float32 reference's own selections at those queries, bool
    [full layers, b, n, pad_to]. `alter` reaches the float32 forward's mixers
    (tests and controls)."""
    seqs = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        if len(seq) > pad_to:
            raise ValueError(f"{len(seq)} tokens exceed pad_to={pad_to}")
        seqs.append(seq + [0] * (pad_to - len(seq)))
    ids = jnp.asarray(seqs, jnp.int32)
    ref, selections = forward(top, layer_fn, ids, model, "float32", rows,
                              alter)
    chosen = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((ids.shape[0], 1), jnp.int32)], axis=1)
    if precision != "float32":
        chosen = jnp.argmax(logits_fn(top, layer_fn, ids, model, precision),
                            axis=-1)
    picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    gaps = jax.device_get(jnp.max(ref, axis=-1) - picked)
    out = []
    for row, prompt, tokens in zip(gaps, prompts, served):
        first = len(prompt) - 1          # logits here predict served[0]
        out.append(row[first:first + len(tokens)].tolist())
    return (out, selections) if rows is not None else out
