"""Plain reference of the ``qwen3_next`` forward pass (tier-1's copy).

``benchmark/references/qwen3-next-80b-a3b-ep2.py`` holds the same text between
its ``reference: begin`` / ``reference: end`` markers, so that a PR which
changes the program cannot change what the benchmark compares it with;
``tests/test_qwen3_next.py`` checks that the two agree.
"""

# --- reference: begin ---------------------------------------------------------
# The forward pass of a cut Qwen3-Next model in straightforward jax.numpy:
# float32, matmuls at "highest" precision, ONE sequence at a time, no paged
# cache, no chunks of the program's, no kernels, every mask built from
# positions, the gated delta rule TOKEN BY TOKEN (a ``lax.scan`` over the
# tokens: nothing of the chunked form), full causal softmax attention, every
# held expert densely. Attention runs in blocks of query positions and the
# experts in blocks of tokens (``query_block``, ``token_block``: the result
# does not depend on them), so the published widths fit beside the program
# under test.
#
# Layer equations (x = rmsnorm(h) of the sublayer's input; every RMS-norm
# weight is zero-centred, y = x^ (1 + w), but the Gated DeltaNet output
# norm's, which is plain):
#   h <- h + mixer(x); h <- h + experts(x); h_0 = embed[token];
#   logits = W_head rmsnorm(h).
#   gated delta (GDN): [q|k|v|z] = x W_qkvz, [b|a] = x W_ba; [q|k|v] through a
#     causal depth-wise conv of K taps (no bias; the K-1 inputs before a
#     continuation are carried), then silu; beta = sigmoid(b); g = -exp(A_log)
#     softplus(a + dt_bias); q, k L2-normalised per head (x / sqrt(sum x^2 +
#     1e-6)), key head j serving value heads j R .. j R + R - 1, q / sqrt(Dk);
#     per value head, S [Dk, Dv] float32:
#       S <- exp(g_t) S; u_t = beta_t (v_t - k_t S); S <- S + k_t^T u_t;
#       o_t = q_t S
#     o <- rmsnorm_head(o) w (plain) * silu(z); y = o W_out.
#   gated attention: q [H, D] = x W_q, gate [H, D] = x W_g (the published
#     q_proj holds both, a head's 2 D outputs as D query and D gate lanes:
#     here they are two matrices side by side), k, v [KH, D]; q, k <-
#     rmsnorm_head (zero-centred); rotary on the first ``rotary`` lanes (lane
#     i paired with lane i + rotary / 2) at ``theta``; causal softmax attention
#     / sqrt(D); y = (o * sigmoid(gate)) W_o.
#   experts: p = softmax(x W_r) over ALL ``n_experts``; the top k, weights
#     renormalised over the k; the sum over the chosen experts THAT ARE HELD
#     (``held`` = [lo, hi): one chip's share) of w_e * W_down_e(silu(W_gate_e x)
#     * W_up_e x), plus ``shared_share`` x sigmoid(x w_sg) * the shared expert
#     (a scalar gate a token). The shares of an expert-parallel group, the
#     shared expert counted once, add up to the uncut layer.
#
# Departures from the published description, each noted: (i) q_proj as two
# matrices (above) and in_proj_qkvz as [q | k | v | z] blocks, not interleaved
# by key head: a permutation of columns of randomly drawn matrices; (ii) the
# multi-token-prediction module is not built; (iii) the cut itself (four
# layers, the held half of the experts, the first half of the vocabulary).
#   * ``degrade``: None is the reference. "state_bf16" rounds the GDN state to
#     bfloat16 after every token; "no_beta" sets beta = 1; "no_decay" sets g =
#     0; "no_l2norm" leaves q and k as the conv gave them; "norm_plain" reads
#     the zero-centred norm weights as plain ones; "no_shared_gate" drops the
#     shared expert's sigmoid gate; "no_attn_gate" the attention's: each exists
#     to show what a lower precision or a wrong law reads against each limit.
# ``carry`` continues a prefix the same function computed: per mixer sublayer
# the prefix's float32 keys and values, or the GDN state and conv tail after
# it, and its length.
import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _bf16_round(a):  # (a cast pair would be optimised away)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def ref_rmsnorm(x, w, eps, zero_centred=True):
    w = _f32(w)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w if zero_centred else w)


def ref_rope(x, theta, rotary, first=0):
    """x [T, H, D] at positions first..first+T-1: the first ``rotary`` lanes
    rotate, the rest pass."""
    T = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))
    ang = ((first + jnp.arange(T, dtype=jnp.float32))[:, None] * inv)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    head = x[..., :rotary]
    rot = jnp.concatenate([-head[..., rotary // 2:], head[..., : rotary // 2]], -1)
    return jnp.concatenate([head * cos + rot * sin, x[..., rotary:]], -1)


def ref_gated_delta(x, w, L, eps, degrade=None, carry=None, length=None):
    """x [T, d] -> (y [T, d], {"S" [H, Dk, Dv], "conv" [K-1, ch]}: the state
    and the conv tail after the last token, or after the first ``length``
    where the rest is padding)."""
    T = x.shape[0]
    H, HK, Dk, Dv, K = L["heads"], L["k_heads"], L["k_dim"], L["head_dim"], L["conv_kernel"]
    kw, ch = HK * Dk, 2 * HK * Dk + H * Dv
    qkvz = x @ _f32(w["w_qkvz"])
    qkv, z = qkvz[:, :ch], qkvz[:, ch:].reshape(T, H, Dv)
    ba = x @ _f32(w["w_ba"])
    beta = jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(_f32(w["A_log"])) * jax.nn.softplus(ba[:, H:] + _f32(w["dt_bias"]))
    if degrade == "no_beta":
        beta = jnp.ones_like(beta)
    if degrade == "no_decay":
        g = jnp.zeros_like(g)
    tail = jnp.zeros((K - 1, ch), jnp.float32) if carry is None else carry["conv"]
    padded = jnp.concatenate([tail, qkv], 0)  # [K-1+T, ch]
    taps = _f32(w["conv_w"])  # [K, ch]; taps[K-1] multiplies the newest input
    act = jax.nn.silu(sum(padded[j : j + T] * taps[j] for j in range(K)))
    n = T if length is None else length
    new_tail = jax.lax.dynamic_slice_in_dim(padded, n, K - 1, 0)

    def unit(a):
        if degrade == "no_l2norm":
            return a
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(act[:, :kw].reshape(T, HK, Dk)) * Dk**-0.5, H // HK, axis=1)
    k = jnp.repeat(unit(act[:, kw : 2 * kw].reshape(T, HK, Dk)), H // HK, axis=1)
    v = act[:, 2 * kw :].reshape(T, H, Dv)

    def token(S, t):  # the rule, one token at a time
        q_t, k_t, v_t, g_t, b_t, real = t
        new = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, new))
        new = new + k_t[:, :, None] * u[:, None, :]
        if degrade == "state_bf16":
            new = _bf16_round(new)
        return jnp.where(real, new, S), jnp.einsum("hk,hkv->hv", q_t, new)

    S0 = jnp.zeros((H, Dk, Dv), jnp.float32) if carry is None else carry["S"]
    S, o = jax.lax.scan(token, S0, (q, k, v, g, beta, jnp.arange(T) < n))
    o = ref_rmsnorm(o, w["o_norm"], eps, zero_centred=False) * jax.nn.silu(z)
    return o.reshape(T, H * Dv) @ _f32(w["w_out"]), {"S": S, "conv": new_tail}


def ref_attention(x, w, L, eps, degrade=None, carry=None, query_block=None, queries=None):
    """x [T, d] -> (y [n, d] at ``queries`` (indices into the T; default all),
    k, v [first + T, KH, D] float32 after the q/k norm and the rotation)."""
    T = x.shape[0]
    H, KH, D = L["heads"], L["kv_heads"], L["head_dim"]
    first = 0 if carry is None else carry["length"]
    zc = degrade != "norm_plain"
    q = ref_rmsnorm((x @ _f32(w["wq"])).reshape(T, H, D), w["q_norm"], eps, zc)
    k = ref_rmsnorm((x @ _f32(w["wk"])).reshape(T, KH, D), w["k_norm"], eps, zc)
    v = (x @ _f32(w["wv"])).reshape(T, KH, D)
    q = ref_rope(q, L["theta"], L["rotary"], first)
    k = ref_rope(k, L["theta"], L["rotary"], first)
    gate = jax.nn.sigmoid(x @ _f32(w["w_gate_attn"])).reshape(T, H, D)
    if degrade == "no_attn_gate":
        gate = jnp.ones_like(gate)
    if carry is not None:
        k, v = jnp.concatenate([carry["k"], k], 0), jnp.concatenate([carry["v"], v], 0)
    at = jnp.arange(T) if queries is None else jnp.asarray(queries)
    q, gate = q[at], gate[at]
    n = q.shape[0]
    pos = first + at
    kg = jnp.repeat(k, H // KH, axis=1)
    vg = jnp.repeat(v, H // KH, axis=1)

    def block(qp):  # a block of queries against every key, masked from positions
        qb, pb = qp
        s = jnp.einsum("qhd,thd->hqt", qb, kg) * D**-0.5
        s = jnp.where(jnp.arange(k.shape[0])[None, None, :] <= pb[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(s, axis=-1), vg)

    qb = query_block or n
    if n <= qb or n % qb:
        o = block((q, pos))
    else:
        o = jax.lax.map(block, (q.reshape(n // qb, qb, H, D), pos.reshape(n // qb, qb)))
        o = o.reshape(n, H, D)
    return (o * gate).reshape(n, H * D) @ _f32(w["wo"]), k, v


def ref_route(x, w, L):
    """(chosen expert ids [T, k], their renormalised weights [T, k])."""
    p = jax.nn.softmax(x @ _f32(w["router_w"]), axis=-1)
    top, idx = jax.lax.top_k(p, L["top_k"])
    return idx, top / (top.sum(-1, keepdims=True) + 1e-20)


def ref_experts(x, w, L, degrade=None, token_block=None):
    """x [T, d] -> [T, d]: the held chosen experts' weighted outputs + the
    gated shared expert's, ``shared_share`` of it."""
    lo = L["held"][0]

    def tokens(xb):
        idx, wt = ref_route(xb, w, L)
        ffn = lambda gate, up, down: (jax.nn.silu(xb @ _f32(gate)) * (xb @ _f32(up))) @ _f32(down)

        def expert(out, e):  # the loop over the experts held, one at a time
            e_id, gate, up, down = e
            share = jnp.where(idx == e_id + lo, wt, 0.0).sum(-1)  # [T], 0 where not chosen
            return out + share[:, None] * ffn(gate, up, down), None

        shared = ffn(w["ws_gate"], w["ws_up"], w["ws_down"])
        if degrade != "no_shared_gate":
            shared = shared * jax.nn.sigmoid(xb @ _f32(w["ws_gate_scalar"]))
        out, _ = jax.lax.scan(
            expert, L.get("shared_share", 1.0) * shared,
            (jnp.arange(w["we_up"].shape[0]), w["we_gate"], w["we_up"], w["we_down"]))
        return out

    T = x.shape[0]
    tb = token_block or T
    if T <= tb or T % tb:
        return tokens(x)
    return jax.lax.map(tokens, x.reshape(T // tb, tb, -1)).reshape(T, -1)


class _Static(dict):
    """A description as a static (hashable) argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _sublayer(h, w, L, eps, degrade, carry, query_block, token_block, queries, length):
    with jax.default_matmul_precision("highest"):
        x = ref_rmsnorm(h, w["norm"], eps, degrade != "norm_plain")
        if L["kind"] == "experts":
            return h + ref_experts(x, w, L, degrade, token_block), None
        if L["kind"] == "gated_delta":
            out, new = ref_gated_delta(x, w, L, eps, degrade, carry, length)
            return h + out, new
        out, k, v = ref_attention(x, w, L, eps, degrade, carry, query_block, queries)
        return (h if queries is None else h[jnp.asarray(queries)]) + out, {"k": k, "v": v}


# dynlint: disable=DYN001 -- the reference is not the serving path: no compile telemetry wanted, and one program per sublayer kind and length is the point
_SUBLAYER = jax.jit(_sublayer, static_argnums=(2, 3, 4, 6, 7))


def reference_forward(weights, layers, tokens, eps, positions=None, degrade=None,
                      carry=None, query_block=None, token_block=None,
                      last_queries_only=False, length=None):
    """tokens [T] (after ``carry``'s prefix, if any) -> {"logits" [n, V] at
    ``positions`` (indices into ``tokens``; default all), "carry": for every
    mixer sublayer the keys and values or the state and conv tail after the
    last token, and the length}. ``last_queries_only``: the LAST attention
    sublayer computes only the queries at ``positions``, and the sublayers
    after it only those rows (valid where no recurrent sublayer follows it:
    nothing after it mixes positions). ``length``: tokens from there on are
    padding (nothing compared sees them: causal), and the carry's GDN states
    are those after the first ``length``."""
    T = len(tokens)
    keep = jnp.arange(T) if positions is None else jnp.asarray(positions)
    first = 0 if carry is None else carry["length"]
    kinds = [L["kind"] for L in layers]
    mixers = [i for i, kind in enumerate(kinds) if kind != "experts"]
    cut = mixers[-1] if last_queries_only and kinds[mixers[-1]] == "attention" else None
    with jax.default_matmul_precision("highest"):
        h = _f32(weights["embed"][jnp.asarray(tokens)])
        new_carry = {"length": first + T}
        for i, (w, L) in enumerate(zip(weights["layers"], layers)):
            prev = None if carry is None or L["kind"] == "experts" else dict(carry[i], length=first)
            h, new = _SUBLAYER(h, w, _Static(L), eps, degrade, prev, query_block, token_block,
                               keep if i == cut else None, length)
            if new is not None:
                new_carry[i] = new
        if cut is None:
            h = h[keep]
        zc = degrade != "norm_plain"
        h = ref_rmsnorm(h, weights["final_norm"], eps, zc)
        return {"logits": h @ _f32(weights["lm_head"]), "carry": new_carry}

# --- reference: end -----------------------------------------------------------


def describe_layers(config, shared_share=1.0):
    """The reference's sublayer descriptions of a ModelConfig."""
    out = []
    for s in config.layer_specs:
        if s.kind == "experts":
            out.append(dict(kind="experts", top_k=s.top_k, held=tuple(s.held_),
                            shared_share=float(shared_share)))
        elif s.kind == "gated_delta":
            out.append(dict(kind="gated_delta", heads=s.n_heads, k_heads=s.n_k_heads,
                            k_dim=s.k_dim, head_dim=s.head_dim, conv_kernel=s.conv_kernel))
        else:
            out.append(dict(kind="attention", heads=s.n_heads, kv_heads=s.n_kv_heads,
                            head_dim=s.head_dim, theta=float(s.rope.theta),
                            rotary=int(s.rope.rotary_dim)))
    return out
