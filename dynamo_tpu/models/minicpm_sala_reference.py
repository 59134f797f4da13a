"""Plain reference of the ``minicpm_sala`` forward pass (tier-1's copy).

``benchmark/references/minicpm-sala-pp4.py`` holds the same text between its
``reference: begin`` / ``reference: end`` markers, so that a PR which changes
the program cannot change what the benchmark compares it with;
``tests/test_sala.py`` checks that the two agree.
"""

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut MiniCPM-SALA model in straightforward jax.numpy:
# float32, matmuls at "highest" precision, ONE sequence at a time, no paged
# cache, no chunks of the program's, no kernels, every mask built from
# positions, the lightning recurrence token by token (a ``lax.scan`` over the
# tokens), the block selection by a full sort. Attention runs in blocks of
# query positions and one K/V head at a time (``query_block``: the result does
# not depend on it), so the published widths fit beside the program under
# test.
#
# Layer equations (x = rmsnorm(h) of the sublayer's input, r = scale_depth /
# sqrt(mup_denominator), the PUBLISHED depth's):
#   h <- h + r * mixer(x); h <- h + r * W_down(silu(W_gate x) * W_up x);
#   h_0 = scale_emb * embed[token]; logits = W_head(rmsnorm(h) / (d / d_base)).
#   lightning: q, k, v = W x as [H, D]; q, k <- rmsnorm_head; rotary (theta,
#     all D lanes, lane i paired with lane i + D/2) on q and k;
#     S_t = lambda_h S_{t-1} + k_t^T v_t (float32), o_t = q_t S_t / sqrt(D);
#     o <- rmsnorm_head(o); y = W_o(o * sigmoid(W_g x)), W_g [d, H D].
#   sparse (minicpm4): q [H, D], k, v [KH, D]; q, k <- rmsnorm_head; no rotary.
#     Compressed key j of K/V head g: mean(k[stride j : stride j + kernel]).
#     A query at position t with t + 1 >= dense_len: p_{h,j} = softmax_j(q_h .
#     Kc_j / sqrt(D)) over the j whose window ends at or before t; a_j = sum
#     of p over the heads of the group; block score b_m = max of a_j over the
#     j whose window meets block m; selected = the first ``init_blocks``
#     blocks, the blocks that hold tokens (t - window, t], and the highest
#     b_m among the rest until ``topk`` blocks are selected (all blocks when
#     the sequence has ``topk`` or fewer); o_h = softmax over the selected
#     blocks' tokens <= t. With t + 1 < dense_len: plain causal attention.
#     y = W_o(o * sigmoid(W_g x)), W_g [d, H D].
#
# Assumed, where the published config names a switch and not its shape (the
# configuration's file lists the same under ``assumed``):
#   1. lambda_h = exp(-2^(-8 (h + 1) / H)), the ALiBi slopes lightning
#      attention is published with, the same in every layer;
#   2. both output gates are per lane (W_g [d, H D]; the full model then
#      counts 9.47 B parameters, per head 8.93 B; the card says "9B");
#   3. the norms' placement: q/k norms before the rotation, the output norm
#      before the gate;
#   4. the sparse sizes (kernel 32, stride 16, block 64, topk 64, init 1,
#      window 2048, dense_len 8192) are the MiniCPM4 family's published
#      ``sparse_config``; the block score is the MAX over the windows that
#      meet the block.
# Departures from the published implementation: (i) the score's softmax is
# exact over the compressed keys (the published CUDA kernels approximate its
# normaliser from coarser keys); (ii) dense or sparse is decided per QUERY, by
# the length of the sequence up to and including it (t + 1 >= dense_len), not
# once per call by the whole prompt's length, so that a token's output does
# not depend on how the prompt was cut into calls (decode agrees with both).
#   * ``degrade``: None is the reference. "state_bf16" rounds the lightning
#     state to bfloat16 after every token; "no_residual_scale", "no_embed_scale"
#     and "no_logit_scale" drop one muP factor; "no_init_block" leaves the
#     forced first block out of the selection; "gate_per_head" gates every
#     lane of a head by the head's first gate lane; "softmax_bf16" rounds the
#     attention's scores, probabilities and sums to bfloat16: each exists to
#     show what a lower precision or a wrong law reads against each limit.
# ``selection`` replaces a sparse layer's own choice (a boolean [T, KH, blocks]
# per sparse sublayer index): logits GIVEN THE PROGRAM'S selection. ``carry``
# continues a prefix the same function computed: per mixer sublayer the
# prefix's float32 keys and values, or the state after it, and its length.
import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def ref_rope(x, theta, first=0):
    """x [T, H, D] at positions first..first+T-1, all D lanes rotate."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = ((first + jnp.arange(T, dtype=jnp.float32))[:, None] * inv)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
    return x * cos + rot * sin


def ref_gate(x, w_g, o, degrade):
    """o [T, H, D] * sigmoid(x W_g) per lane."""
    g = jax.nn.sigmoid(x @ _f32(w_g)).reshape(o.shape)
    if degrade == "gate_per_head":
        g = jnp.broadcast_to(g[..., :1], o.shape)
    return o * g


def ref_lightning(x, w, L, eps, degrade=None, carry=None, length=None):
    """x [T, d] -> (y [T, d], the state [H, D, D] after the last token, or
    after the first ``length`` where the rest is padding)."""
    T = x.shape[0]
    H, D = L["heads"], L["head_dim"]
    first = 0 if carry is None else carry["length"]
    q = ref_rmsnorm((x @ _f32(w["wq"])).reshape(T, H, D), w["q_norm"], eps)
    k = ref_rmsnorm((x @ _f32(w["wk"])).reshape(T, H, D), w["k_norm"], eps)
    v = (x @ _f32(w["wv"])).reshape(T, H, D)
    q, k = ref_rope(q, L["theta"], first), ref_rope(k, L["theta"], first)
    lam = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(H, dtype=jnp.float32) + 1) / H)))

    def token(S, qkv):  # the recurrence, one token at a time
        q_t, k_t, v_t, real = qkv
        new = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]  # [H, Dk, Dv]
        if degrade == "state_bf16":
            new = _bf16_round(new)
        return jnp.where(real, new, S), jnp.einsum("hk,hkv->hv", q_t, new) * D**-0.5

    S0 = jnp.zeros((H, D, D), jnp.float32) if carry is None else carry["S"]
    real = jnp.arange(T) < (T if length is None else length)
    S, o = jax.lax.scan(token, S0, (q, k, v, real))
    o = ref_gate(x, w["w_gate_attn"], ref_rmsnorm(o, w["o_norm"], eps), degrade)
    return o.reshape(T, H * D) @ _f32(w["wo"]), S


def ref_compressed_keys(k, L):
    """k [T, KH, D] -> Kc [J, KH, D], J = the complete windows:
    Kc_j = mean(k[stride j : stride j + kernel])."""
    T = k.shape[0]
    J = max((T - L["kernel"]) // L["stride"] + 1, 0)
    at = (jnp.arange(J) * L["stride"])[:, None] + jnp.arange(L["kernel"])[None]
    return k[at].mean(1) if J else jnp.zeros((0,) + k.shape[1:], jnp.float32)


def ref_block_scores(q, kc, q_pos, L, n_blocks):
    """q [Q, H, D] at positions q_pos [Q], kc [J, KH, D] -> b [Q, KH, blocks]:
    the block scores (-inf for a block no complete window at or before the
    query meets)."""
    Q, H, D = q.shape
    J, KH = kc.shape[0], kc.shape[1]
    if J == 0:
        return jnp.full((Q, KH, n_blocks), -jnp.inf)
    st, kn, bl = L["stride"], L["kernel"], L["block"]
    qg = q.reshape(Q, KH, H // KH, D)
    s = jnp.einsum("qgnd,jgd->qgnj", qg, kc) * D**-0.5
    j = jnp.arange(J)
    ended = ((j * st + kn - 1)[None, :] <= jnp.asarray(q_pos)[:, None])[:, None, None]  # [Q,1,1,J]
    top = jnp.max(jnp.where(ended, s, -1e30), -1, keepdims=True)
    p = jnp.where(ended, jnp.exp(jnp.minimum(s - top, 0.0)), 0.0)
    a = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(2)  # [Q, KH, J]
    a = jnp.where(ended[:, :, 0], a, -jnp.inf)
    # Window j covers tokens [st j, st j + kn), block m [bl m, bl m + bl):
    # they meet for j from floor((bl m - kn) / st) + 1 to ceil(bl (m + 1) / st) - 1.
    m = jnp.arange(n_blocks)
    j_lo = (bl * m - kn) // st + 1
    width = -(-bl // st) + -(-kn // st)
    at = j_lo[:, None] + jnp.arange(width)[None]  # [blocks, width]
    meets = (at >= 0) & (at < J) & (at * st < (m[:, None] + 1) * bl) & (at * st + kn > m[:, None] * bl)
    vals = jnp.where(meets[None, None], a[:, :, jnp.clip(at, 0, J - 1)], -jnp.inf)
    return vals.max(-1)


def ref_forced(q_pos, L, n_blocks, degrade=None):
    """[Q, blocks]: the first blocks and those of the last ``window`` tokens."""
    t = jnp.asarray(q_pos)[:, None]
    m = jnp.arange(n_blocks)[None, :]
    local = (m >= jnp.maximum(t - L["window"] + 1, 0) // L["block"]) & (m <= t // L["block"])
    first = m < (0 if degrade == "no_init_block" else L["init_blocks"])
    return (first & (m <= t // L["block"])) | local


def ref_select(b, q_pos, L, degrade=None):
    """b [Q, KH, blocks] -> the selected set, boolean [Q, KH, blocks], by a
    full sort: forced blocks first, then the best scores, ``topk`` in all."""
    n_blocks = b.shape[-1]
    forced = ref_forced(q_pos, L, n_blocks, degrade)[:, None]
    seen = (jnp.arange(n_blocks)[None, :] <= jnp.asarray(q_pos)[:, None] // L["block"])[:, None]
    key = jnp.where(forced, jnp.inf, jnp.where(seen, jnp.nan_to_num(b, neginf=-1e30), -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < L["topk"]) & seen


def ref_sparse_attention(x, w, L, eps, degrade=None, carry=None, selection=None,
                         query_block=None, queries=None, want=False):
    """x [T, d] -> (y [T, d], k [T0 + T, KH, D], v). ``queries`` (indices into
    x): only those rows of y are computed (the others are zeros). ``want``:
    also return {"scores" b, "selected", "heads" o [T, H, D] after the gate}
    at the computed queries."""
    T = x.shape[0]
    H, KH, D = L["heads"], L["kv_heads"], L["head_dim"]
    first = 0 if carry is None else carry["length"]
    low = _bf16_round if degrade == "softmax_bf16" else (lambda a: a)
    q = ref_rmsnorm((x @ _f32(w["wq"])).reshape(T, H, D), w["q_norm"], eps)
    k = ref_rmsnorm((x @ _f32(w["wk"])).reshape(T, KH, D), w["k_norm"], eps)
    v = (x @ _f32(w["wv"])).reshape(T, KH, D)
    if carry is not None:
        k, v = jnp.concatenate([carry["k"], k], 0), jnp.concatenate([carry["v"], v], 0)
    Tk = k.shape[0]
    n_blocks = -(-Tk // L["block"])
    pad = n_blocks * L["block"] - Tk
    k_pad = jnp.concatenate([k, jnp.zeros((pad, KH, D), jnp.float32)], 0)
    v_pad = jnp.concatenate([v, jnp.zeros((pad, KH, D), jnp.float32)], 0)
    kc = ref_compressed_keys(k, L)
    rows = jnp.arange(T) if queries is None else jnp.asarray(queries)
    n = rows.shape[0]
    QB = min(query_block or n, n) or 1
    n_pad = -(-n // QB) * QB
    rows_p = jnp.concatenate([rows, jnp.full((n_pad - n,), rows[-1] if n else 0)])
    t_key = jnp.arange(n_blocks * L["block"])

    def block(r):  # QB queries against every key, masked from positions
        idx = jax.lax.dynamic_slice_in_dim(rows_p, r, QB)
        q_pos = first + idx
        qb = q[idx]
        b = ref_block_scores(qb, kc, q_pos, L, n_blocks)
        chosen = ref_select(b, q_pos, L, degrade)
        if selection is not None:
            chosen = selection[idx]
        dense = (q_pos + 1 < L["dense_len"])[:, None, None]
        per_key = jnp.repeat(chosen | dense, L["block"], axis=-1)  # [QB, KH, keys]
        seen = per_key & (t_key[None, None, :] <= q_pos[:, None, None])
        out = []
        for g in range(KH):  # one K/V head at a time
            s = low(jnp.einsum("qnd,td->qnt", qb.reshape(QB, KH, H // KH, D)[:, g], k_pad[:, g]) * D**-0.5)
            s = jnp.where(seen[:, g, None, :], s, -jnp.inf)
            p = low(jnp.exp(s - s.max(-1, keepdims=True)))
            p = low(p / low(p.sum(-1, keepdims=True)))
            out.append(low(jnp.einsum("qnt,td->qnd", p, v_pad[:, g])))
        return jnp.stack(out, 1).reshape(QB, H, D), b, chosen

    o, b, chosen = jax.lax.map(block, jnp.arange(0, n_pad, QB))
    o = o.reshape(n_pad, H, D)[:n]
    o = ref_gate(x[rows], w["w_gate_attn"], o, degrade)
    y = jnp.zeros((T, H * D), jnp.float32).at[rows].set(o.reshape(n, H * D)) @ _f32(w["wo"])
    extra = None
    if want:
        extra = {"scores": b.reshape(n_pad, KH, n_blocks)[:n],
                 "selected": chosen.reshape(n_pad, KH, n_blocks)[:n], "heads": o}
    return y, k, v, extra


def ref_dense_ffn(x, w, token_block=None):
    """Gated-silu FFN, in blocks of tokens. x [T, d] -> [T, d]."""
    f = lambda xb: (jax.nn.silu(xb @ _f32(w["w_gate"])) * (xb @ _f32(w["w_up"]))) @ _f32(w["w_down"])
    T = x.shape[0]
    tb = token_block or T
    if T <= tb or T % tb:
        return f(x)
    return jax.lax.map(f, x.reshape(T // tb, tb, -1)).reshape(T, -1)


class _Static(dict):
    """A description as a static (hashable) argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _mixer(h, w, L, model, degrade, carry, selection, query_block, queries, want, length=None):
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], model["eps"])
        if L["kind"] == "lightning":
            out, S = ref_lightning(x, w, L, model["eps"], degrade, carry, length)
            new, extra = {"S": S}, None
        else:
            out, k, v, extra = ref_sparse_attention(
                x, w, L, model["eps"], degrade, carry, selection, query_block, queries, want)
            new = {"k": k, "v": v}
        r = 1.0 if degrade == "no_residual_scale" else model["residual"]
        return h + r * out, new, extra


def _ffn(h, w, model, degrade, token_block):
    with jax.default_matmul_precision("highest"):
        r = 1.0 if degrade == "no_residual_scale" else model["residual"]
        return h + r * ref_dense_ffn(ref_rmsnorm(h, w["norm"], model["eps"]), w, token_block)


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_MIXER = jax.jit(_mixer, static_argnums=(2, 3, 4, 7, 9))
# dynlint: disable=DYN001 -- as above
_FFN = jax.jit(_ffn, static_argnums=(2, 3, 4))


def reference_forward(weights, layers, tokens, model, positions=None, degrade=None,
                      carry=None, selection=None, query_block=None, token_block=None,
                      last_queries_only=False, want=(), length=None):
    """tokens [T] (after ``carry``'s prefix, if any) -> {"logits" [n, V] at
    ``positions`` (indices into ``tokens``; default all), "carry": for every
    mixer sublayer the keys and values or the state after the last token, and
    the length, "extra": for each sparse sublayer index in ``want`` its block
    scores, selected set and gated per-head output at ``positions``}.
    ``last_queries_only``: the LAST sparse sublayer computes only the queries
    at ``positions`` (nothing after it mixes positions; the others' rows are
    not needed). ``length``: tokens from there on are padding (nothing compared
    sees them: causal), and the carry's lightning states are those after the
    first ``length``. ``model`` = {"eps", "embed", "residual", "logit_divisor"}."""
    T = len(tokens)
    keep = jnp.arange(T) if positions is None else jnp.asarray(positions)
    model = _Static(model)
    first = 0 if carry is None else carry["length"]
    mixers = [i for i, L in enumerate(layers) if L["kind"] != "ffn"]
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        if degrade != "no_embed_scale":
            h = h * model["embed"]
        new_carry, extra = {"length": first + T}, {}
        for i, (w, L) in enumerate(zip(weights["layers"], layers)):
            if L["kind"] == "ffn":
                h = _FFN(h, w, model, degrade, token_block)
                continue
            only = keep if (
                last_queries_only and i == mixers[-1] and L["kind"] == "sparse") else None
            prev = None if carry is None else dict(carry[i], length=first)
            h, new_carry[i], ex = _MIXER(
                h, w, _Static(L), model, degrade, prev,
                None if selection is None else selection.get(i), query_block,
                only, i in want, length)
            if ex is not None:
                extra[i] = ex if only is not None else jax.tree.map(lambda a: a[keep], ex)
        h = ref_rmsnorm(h[keep], weights["final_norm"], model["eps"])
        if degrade != "no_logit_scale":
            h = h / model["logit_divisor"]
        return {"logits": h @ _f32(weights["lm_head"]), "carry": new_carry, "extra": extra}

# --- reference: end -----------------------------------------------------------


def describe_layers(config):
    """The reference's sublayer descriptions of a ModelConfig."""
    out = []
    for s in config.layer_specs:
        if s.kind == "dense_ffn":
            out.append(dict(kind="ffn"))
        elif s.kind == "lightning":
            out.append(dict(kind="lightning", heads=s.n_heads, head_dim=s.head_dim,
                            theta=float(s.rope_theta)))
        else:
            sp = s.sparse
            out.append(dict(
                kind="sparse", heads=s.n_heads, kv_heads=s.n_kv_heads, head_dim=s.head_dim,
                kernel=sp.kernel, stride=sp.stride, block=sp.block, topk=sp.topk,
                init_blocks=sp.init_blocks, window=sp.window, dense_len=sp.dense_len))
    return out


def describe_model(config):
    return dict(eps=float(config.rms_norm_eps), embed=float(config.embed_multiplier),
                residual=float(config.residual_multiplier),
                logit_divisor=float(config.logit_divisor))
