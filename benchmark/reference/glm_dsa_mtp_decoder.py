"""The plain reference of a latent-attention decoder that DRAFTS with its
multi-token-prediction (MTP) module: every layer multi-head latent attention
(MLA) whose keys a learned indexer chooses, a leading dense layer and sparse
experts with a shared expert after it, one MTP module behind the last layer,
and the self-drafting loop that verifies the module's draft by token match, in
`jax.numpy`.

Written from the published configuration of GLM-5 (`config.json`,
`model_type: glm_moe_dsa`: `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `index_n_heads`, `index_head_dim`,
`index_topk`, `rope_parameters`, `first_k_dense_replace`, `n_routed_experts`,
`scoring_func`, `routed_scaling_factor`, `num_nextn_predict_layers`, ...), of
multi-head latent attention (DeepSeek-V2), of the lightning indexer
(DeepSeek-V3.2-Exp, whose key names the config uses) and of the MTP module as
DeepSeek-V3 publishes it and GLM-4.5's public modelling code names its parts
(`enorm`, `hnorm`, `eh_proj`, one decoder layer, `shared_head`). float32
throughout, every matrix multiplication at `highest` precision. Attention is
the PROJECTED form (keys and values of every head made from the latent, no
absorption) under an explicit visibility mask built from dense index scores
and an exact top-k; the experts run one at a time under `lax.scan` over the
held ones; there is no cache, no kernel, no batching. It imports nothing from
`llama_pipeline_parallel_tpu`; the indexer's scores, the exact selection, the
router and the expert layer are `latent_moe_decoder`'s (the same published
equations under the same key names), called.

Pre-norm residual block, RMSNorm eps 1e-5: `h += attn(norm(h)); h +=
ffn(norm(h))`; after the last layer `logits = norm(h) W_head`.

Mixer (H 64, latents 2048 / 512, nope 192 + rope 64, v 256):
    cq = rmsnorm(W_qa x);  [q^N_h; q^R_h] = W_qb,h cq,  q^R roped
    [c; k^R] = W_kva x;  c = rmsnorm(c),  k^R roped, shared by the heads
    k_h,s = [W_kb,h^K c_s; k^R_s],  v_h,s = W_kb,h^V c_s
    o_h,t = sum_{s in S_t} softmax_s(q_h,t . k_h,s / sqrt(nope + rope)) v_h,s
    y_t = W_o [o_h,t]_h                 no gate, no rescale of the latents
Indexer (32 heads of 128, rope on the first 64 numbers):
    qI_t,j = W_qI,j cq_t;  kI_s = layernorm(W_kI x_s);  w_t = W_w x_t / sqrt(32 * 128)
    I_t,s = sum_j w_t,j relu(qI_t,j . kI_s)   for s <= t
    S_t = {t} and the largest I_t,s until there are `index_topk`
Feed-forward: layer 0 a SwiGLU of `intermediate_size`; later layers `s =
sigmoid(W_r x)`, the k largest of `s + bias`, a selected expert's weight `s_e
/ sum of the selected s` x `routed_scaling_factor`, `y = sum_selected w_e
SwiGLU_e(x) + SwiGLU_shared(x)`.

MTP module (one), for a position i whose NEXT token t_{i+1} is known:
    u_i = [enorm(E[t_{i+1}]) | hnorm(h_i)] W_eh
    m_i = layer(u_i)         one whole layer as above (MLA + indexer over the
                             module's OWN keys, experts + shared)
    logits^mtp_i = rmsnorm_sh(m_i) W_head          a distribution for t_{i+2}
The draft is its argmax.

Self-drafting (`self_draft`), exactly: with t_{p+1} emitted and the draft d
for t_{p+2}, run the trunk over the sequence with d appended; draw y from the
logits at p + 1; if y == d, the logits at p + 2 are the model's own for
t_{p+3}: draw a second token from them. A token is only ever drawn from
logits of a prefix of emitted tokens, so the stream is that of one-token
decoding under the same draws.

Readings of what `config.json` leaves open (the configuration file lists
them under `assumed`):
- the embedding's half comes first in the concatenation (DeepSeek-V3's
  order), `h_i` is the last layer's output BEFORE the trunk's final norm,
  table and head are the trunk's own (one copy), the module's layer has an
  indexer like the rest;
- rotate-half rope for the published interleaved one (a fixed permutation of
  a head's rope numbers under seeded weights), base `rope_parameters.
  rope_theta`, no scaling;
- `kI`'s norm is a LayerNorm with bias; the indexer's Hadamard rotation is
  left out (orthogonal: it changes no qI . kI); the query's own position is
  always selected: it takes one of the `index_topk` places;
- `topk_method: noaux_tc` with `n_group` 1: the selection bias is zero under
  seeded weights and the one group restricts nothing;
- `first_k_dense_replace` counts leading dense layers of the published
  depth; the file runs `first_k_dense_replace` of the layers it has as dense;
- the layer is told which experts it holds (`expert_offset`,
  `n_routed_experts`): it routes over all of `router_experts`, adds the
  terms of the held ones and leaves the others out.

`precision="fp8"` is the CONTROL (see `dense_decoder`): every weight
multiplication but the router's and the indexer's as a float8 recipe
computes it. `alter` names departures made on purpose (tests and controls):
`most_recent` selects the most recent `index_topk` positions instead of the
largest scores; `unshifted` feeds the module E[t_i] in the place of
E[t_{i+1}].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import HIGHEST, _mm, rms_norm, rotary
from benchmark.reference.latent_moe_decoder import (
    _by_query_blocks,
    _swiglu,
    index_scores,
    moe_layer,
    select,
)


def dims(model: dict) -> dict:
    """The numbers of a configuration file the decoder needs, as a flat dict
    of hashable values under the names `latent_moe_decoder`'s functions
    read (`f_*`: the one kind of mixer, `i_*`: its indexer)."""
    if model["scoring_func"] != "sigmoid":
        raise ValueError("the router scores with a sigmoid")
    if model.get("num_nextn_predict_layers", 0) != 1:
        raise ValueError("this decoder has exactly one MTP module")
    theta = model["rope_theta"] if "rope_theta" in model else \
        model["rope_parameters"]["rope_theta"]
    return {
        "d": model["hidden_size"], "layers": model["num_hidden_layers"],
        "dense": model["first_k_dense_replace"],
        "vocab": model["vocab_size"], "eps": model["rms_norm_eps"],
        "f_heads": model["num_attention_heads"],
        "f_rq": model["q_lora_rank"], "f_rkv": model["kv_lora_rank"],
        "f_nope": model["qk_nope_head_dim"], "f_rope": model["qk_rope_head_dim"],
        "f_v": model["v_head_dim"], "f_theta": float(theta),
        "f_rq_scale": 1.0, "f_rkv_scale": 1.0,
        "i_heads": model["index_n_heads"], "i_hd": model["index_head_dim"],
        "topk": model["index_topk"],
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


def mla_mixer(mixer, x, positions, dm: dict, precision: str,
              alter: tuple = ()):
    """One mixer's output [b, s, d] and the visibility mask it attended
    under [b, t, s]."""
    b, s, _ = x.shape
    H, nope, rope, v_dim = dm["f_heads"], dm["f_nope"], dm["f_rope"], dm["f_v"]
    rkv, theta = dm["f_rkv"], dm["f_theta"]
    cq = rms_norm(_mm(x, mixer["wqa"], precision), mixer["q_norm"], dm["eps"])
    q = _mm(cq, mixer["wqb"], precision).reshape(b, s, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], positions, theta)], axis=-1)
    ckv = _mm(x, mixer["wkva"], precision)
    c = rms_norm(ckv[..., :rkv], mixer["kv_norm"], dm["eps"])
    k_rope = rotary(ckv[..., None, rkv:], positions, theta)
    k_nope = _mm(c, mixer["wkb_k"].reshape(rkv, H * nope),
                 precision).reshape(b, s, H, nope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, H, rope))], axis=-1)
    v = _mm(c, mixer["wkb_v"].reshape(rkv, H * v_dim),
            precision).reshape(b, s, H, v_dim)
    scores = index_scores(mixer, x, cq, positions, dm)
    if "most_recent" in alter:          # the wrong selection, on purpose
        scores = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32),
                                  scores.shape)
    mask = select(scores, dm["topk"])

    def block(args):
        q_blk, m_blk = args                       # [b, B, H, hd], [b, B, s]
        dots = jnp.einsum("bthd,bshd->bhts", q_blk, k, precision=HIGHEST)
        dots = jnp.where(m_blk[:, None], dots * (nope + rope) ** -0.5, -jnp.inf)
        # a block's padding rows see nothing: keep them finite
        dots = jnp.where(jnp.any(m_blk, -1)[:, None, :, None], dots, 0.0)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(dots, axis=-1), v,
                          precision=HIGHEST)

    out = _by_query_blocks(block, (q, mask), s).reshape(b, s, H * v_dim)
    return _mm(out, mixer["wo"], precision), mask


def block(layer, x, positions, dm: dict, precision: str = "float32",
          alter: tuple = (), shared: bool = True):
    """One layer: (x, its visibility mask). Dense when it has `mlp`, sparse
    when it has `moe` (`shared=False` leaves the shared expert out: the
    shares of several chips count it once)."""
    h = rms_norm(x, layer["input_norm"], dm["eps"])
    mixed, mask = mla_mixer(layer["mixer"], h, positions, dm, precision, alter)
    x = x + mixed
    h = rms_norm(x, layer["post_norm"], dm["eps"])
    if "mlp" in layer:
        m = layer["mlp"]
        return x + _swiglu(h, m["gate"], m["up"], m["down"], precision), mask
    return x + moe_layer(layer["moe"], h, dm, precision, shared), mask


@functools.partial(jax.jit, static_argnames=("dm_items", "precision", "alter"))
def _block_jit(layer, x, positions, rows, *, dm_items, precision, alter):
    x, mask = block(layer, x, positions, dict(dm_items), precision, alter)
    # only the rows asked for leave the program: [b, n, s]
    return x, jnp.take_along_axis(mask, rows[..., None], axis=1)


def _freeze(dm: dict) -> tuple:
    return tuple(sorted(dm.items()))


def mtp_input(mtp: dict, embed, ids, hidden, dm: dict, precision: str,
              alter: tuple = ()):
    """u_i for every position of [b, s]: position i takes E[ids[i + 1]] (the
    last position, which has no next token, takes E[ids[0]]: its row means
    nothing and no earlier position sees it)."""
    nxt = ids if "unshifted" in alter else jnp.roll(ids, -1, axis=1)
    both = jnp.concatenate(
        [rms_norm(embed[nxt], mtp["enorm"], dm["eps"]),
         rms_norm(hidden, mtp["hnorm"], dm["eps"])], axis=-1)
    return _mm(both, mtp["eh_proj"], precision)


def trunk_logits(top: dict, hidden, model: dict, precision: str = "float32"):
    """The trunk's logits of hidden states [..., d] (the last layer's
    output): the final norm, then the head."""
    return _mm(rms_norm(hidden, top["norm"], model["rms_norm_eps"]),
               top["lm_head"], precision)


def module_logits(top: dict, hidden, model: dict, precision: str = "float32"):
    """The module's logits of ITS layer's output [..., d]: its own norm, then
    the trunk's head."""
    return _mm(rms_norm(hidden, top["mtp"]["shared_head_norm"],
                        model["rms_norm_eps"]), top["lm_head"], precision)


def forward(top: dict, layer_fn, ids, model: dict, precision: str = "float32",
            rows=None, alter: tuple = (), module: bool = True,
            heads: bool = True) -> dict:
    """[b, s] token ids -> {"logits" [b, s, vocab]: the trunk's;
    "mtp_logits" [b, s, vocab]: the module's at every position given the
    token AFTER it (position i predicts token i + 2; the last position's row
    means nothing); "hidden" [b, s, d]: the last layer's output before the
    final norm; "selections" / "mtp_selections": with `rows` [b, n] query
    positions, the visibility mask of those queries in every trunk layer,
    bool [layers, b, n, s], and in the module's layer [b, n, s]}. `top` holds
    `embed`, `norm`, `lm_head` and `mtp` (`enorm`, `hnorm`, `eh_proj`,
    `shared_head_norm`); `layer_fn(i)` gives layer `i`'s weights in float32,
    one layer at a time, the module's layer at i == `num_hidden_layers`.
    Requests run one at a time inside a layer, so a layer's weights are made
    once for all of them. `module=False` stops after the trunk;
    `heads=False` leaves both logits out and returns the module's layer's
    output beside "hidden" ("mtp_hidden"), for a caller that reads logits at
    a few positions of long sequences (`trunk_logits`, `module_logits`)."""
    dm = dims(model)
    ids = jnp.asarray(ids, jnp.int32)
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (1, s))
    asked = jnp.zeros((b, 1), jnp.int32) if rows is None else jnp.asarray(rows)
    run = functools.partial(_block_jit, dm_items=_freeze(dm),
                            precision=precision, alter=tuple(alter))

    def through(layer, xs):
        out = [run(layer, xs[r], positions, asked[r:r + 1]) for r in range(b)]
        return [x for x, _ in out], jnp.concatenate([m for _, m in out])

    xs = [top["embed"][ids[i:i + 1]] for i in range(b)]
    masks = []
    for i in range(dm["layers"]):
        # wait for the layer: buffers are handed out when work is enqueued,
        # and a host that runs ahead keeps several layers' weights alive
        xs, mask = jax.block_until_ready(through(layer_fn(i), xs))
        masks.append(mask)
    hidden = jnp.concatenate(xs, axis=0)
    out = {"hidden": hidden,
           "selections": jnp.stack(masks) if rows is not None else None}
    if heads:
        out["logits"] = trunk_logits(top, hidden, model, precision)
    if not module:
        return out
    mtp = top["mtp"]
    us = [mtp_input(mtp, top["embed"], ids[r:r + 1], xs[r], dm, precision,
                    alter) for r in range(b)]
    ms, mask = through(layer_fn(dm["layers"]), us)
    out["mtp_hidden"] = jnp.concatenate(ms, axis=0)
    if heads:
        out["mtp_logits"] = module_logits(top, out["mtp_hidden"], model,
                                          precision)
    out["mtp_selections"] = mask if rows is not None else None
    return out


def logits_fn(top: dict, layer_fn, ids, model: dict,
              precision: str = "float32", alter: tuple = ()):
    return forward(top, layer_fn, ids, model, precision, alter=alter,
                   module=False)["logits"]


def greedy(logits, index: int) -> int:
    return int(jnp.argmax(logits))


def self_draft(top: dict, layer_fn, prompt: list, steps: int, model: dict,
               choose=greedy, drafting: bool = True) -> dict:
    """The plain self-drafting loop over ONE sequence, everything recomputed
    from scratch at every step. `choose(logits [vocab], n)` draws the n-th
    emitted token (the default takes the largest). Returns {"tokens": the
    `steps` tokens emitted; "accepted": one bool a verify step, whether its
    draft was the token drawn; "drafts": the drafts themselves}.
    `drafting=False` is one-token decoding under the same draws: the same
    tokens."""
    tokens = [choose(logits_fn(top, layer_fn, [list(prompt)], model)[0, -1],
                     0)]
    accepted, drafts = [], []
    while len(tokens) < steps:
        seq = list(prompt) + tokens
        n = len(seq)
        if not drafting:
            tokens.append(choose(
                logits_fn(top, layer_fn, [seq], model)[0, -1], len(tokens)))
            continue
        # the module at the position before the last token, which it is
        # given as that position's next: a draft for the token after it
        draft = int(jnp.argmax(
            forward(top, layer_fn, [seq], model)["mtp_logits"][0, n - 2]))
        logits = logits_fn(top, layer_fn, [seq + [draft]], model)[0]
        first = choose(logits[n - 1], len(tokens))
        tokens.append(first)
        drafts.append(draft)
        accepted.append(first == draft)
        if first == draft and len(tokens) < steps:
            tokens.append(choose(logits[n], len(tokens)))
    return {"tokens": tokens, "accepted": accepted, "drafts": drafts}


def served_token_gaps(top: dict, layer_fn, prompts: list, served: list,
                      model: dict, pad_to: int, precision: str = "float32",
                      rows=None, alter: tuple = ()) -> dict:
    """As `latent_moe_decoder.served_token_gaps`, for the trunk AND the
    module: prompt + served tokens padded at the END to `pad_to`, which no
    causal mask looks at. Per request, for each served token k >= 0, the
    float32 reference's best TRUNK logit at the position that predicts it
    minus its logit of the served token ("gaps"), and for each served token k
    >= 1 the same of the MODULE's logits at the position that predicts it
    (position prompt - 2 + k, which is given served token k - 1 as its next:
    "mtp_gaps"; it says how well the module, fed the served prefix, ranks the
    served token, which is what a draft is). Under a lower `precision` the
    gaps are those of the tokens that precision puts first; `alter` reaches
    the float32 forward. With `rows` ([b, n] query positions) also the
    reference's own selections there ("selections", "mtp_selections")."""
    seqs = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        if len(seq) > pad_to:
            raise ValueError(f"{len(seq)} tokens exceed pad_to={pad_to}")
        seqs.append(seq + [0] * (pad_to - len(seq)))
    ids = jnp.asarray(seqs, jnp.int32)
    ref = forward(top, layer_fn, ids, model, "float32", rows, alter)
    shift = lambda n: jnp.concatenate(
        [ids[:, n:], jnp.zeros((ids.shape[0], n), jnp.int32)], axis=1)
    chosen = {"logits": shift(1), "mtp_logits": shift(2)}
    if precision != "float32":
        low = forward(top, layer_fn, ids, model, precision)
        chosen = {name: jnp.argmax(low[name], axis=-1) for name in chosen}
    gaps = {}
    for name, picked in chosen.items():
        at = jnp.take_along_axis(ref[name], picked[..., None], axis=-1)[..., 0]
        gaps[name] = jax.device_get(jnp.max(ref[name], axis=-1) - at)
    out = {"gaps": [], "mtp_gaps": [], "selections": ref["selections"],
           "mtp_selections": ref.get("mtp_selections")}
    for r, (prompt, tokens) in enumerate(zip(prompts, served)):
        first = len(prompt) - 1          # trunk logits here predict served[0]
        out["gaps"].append(
            gaps["logits"][r, first:first + len(tokens)].tolist())
        # module logits at `first` predict served[1], given served[0]
        out["mtp_gaps"].append(
            gaps["mtp_logits"][r, first:first + len(tokens) - 1].tolist())
    return out
