"""Plain float32 reference of Moonlight-16B-A3B (DeepSeek-V3 architecture)
as one chip of an 8-way expert-parallel deployment holds it, and of its
training step.

Straightforward ``jax.numpy`` with every matrix product at ``highest``
precision; no kernels, no routing plan, no chunked online softmax. Sizes come
from the configuration file's ``model`` section, the optimizer from its
``train`` section. Settings the ``model`` section does not name are this
file's own published constants (``PUBLISHED``, from the model's config.json
and the DeepSeek-V3 report); at the published widths they are asserted
against the program's parameter shapes, at any other size they are read from
those shapes. The weights are the harness's, laid out as the program's
checkpoint is: ``emb``, ``unembed``, ``final_norm``, the dense first layer
``stack/segments/0/e0`` and the stacked MoE layers ``stack/segments/1/e0``.

Layer (pre-norm, RMSNorm): x += MLA(RMS1(x)); x += FFN(RMS2(x)).
MLA (DeepSeek-V2 Sec. 2.1, no query latent): q = h W_q per head
[nope | rope]; [c | k_pe] = h W_kv_a; c = RMS(c); [k_nope | v] = c W_kv_b per
head; RoPE (theta rope_theta, rotation of the two halves) on q's rope part
and on k_pe, which every head shares; causal softmax of q.k * head_dim**-0.5
over v, computed one block of queries at a time; then W_o.
FFN of the first layer: W2 (silu(x W1) * x W3). MoE layers (DeepSeek-V3 Sec.
2.1.2): s = sigmoid(x W_g) over the router's experts; the top-k by s + b (b
the selection bias, no gradient); weights s_sel / sum s_sel * routed scale;
y = sum over the held experts [0, n_experts) of w_e E_e(x) (every held expert
computed densely over every token and masked) + the shared experts' sum,
E(x) = W2 (silu(x W1) * x W1g). Loss: mean next-token cross entropy over the
real vocabulary + reg_gamma * the sequence-wise balance loss of each MoE
layer (per sequence of T tokens sum_e f_e P_e, f_e = E/(k T) * tokens choosing
e, P_e the mean of s / sum s; mean over sequences). Step: gradient, clip to
the global norm, AdamW with the cosine schedule, then each MoE layer's b
moves by bias_rate * sign(mean load - load) with the step's loads.

``precision="fp8"`` is the control that the check has to fail: the same
model computed in float8 e4m3, the precision below the program's bfloat16.
Every product's operands and result, the embedding and the residual stream
after each sublayer are rounded to float8 (per-tensor scale), and so is each
of their gradients on the way back.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 240.0     # largest finite value of 4 exponent and 3 mantissa bits
QUERY_BLOCK = 512  # queries scored at a time
# hf:moonshotai/Moonlight-16B-A3B config.json; bias rate and balance-loss
# strength from DeepSeek-V3 (arXiv:2412.19437) Sec. 4.2
PUBLISHED = {"d_model": 2048, "router": 64, "kv_lora_rank": 512,
             "rope_head_dim": 64, "v_head_dim": 128, "dense_d_ff": 11264,
             "first_dense_layers": 1, "routed_scale": 2.446,
             "bias_rate": 1e-3, "balance_alpha": 1e-4}


def _round8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, in float32
    (``reduce_precision``: XLA may drop a pair of converts)."""
    scale = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


@jax.custom_vjp
def _fp8(x):
    return _round8(x)


_fp8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _carry(precision):
    return _fp8 if precision == "fp8" else (lambda x: x)


def _mm(precision):
    q = _carry(precision)

    def mm(spec, a, b):
        return q(jnp.einsum(spec, q(a), q(b)))
    return mm


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x (B, S, H, D): rotate the pairs (x[i], x[i + D/2]) by position *
    theta**(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def settings(model, shapes):
    """The reference's sizes: the file's, and the published constants,
    checked against (at published widths) or read from the parameters."""
    moe = shapes["stack"]["segments"][1]["e0"]
    dense = shapes["stack"]["segments"][0]["e0"]
    nh, dqk = model["n_heads"], model["head_dim"]
    got = {"router": moe["ffn"]["router"].shape[-1],
           "kv_lora_rank": moe["attn"]["wkv_b"].shape[-2],
           "rope_head_dim": (moe["attn"]["wkv_a"].shape[-1]
                             - moe["attn"]["wkv_b"].shape[-2]),
           "dense_d_ff": dense["ffn"]["w1"].shape[-1],
           "first_dense_layers": dense["ffn"]["w1"].shape[0]}
    got["v_head_dim"] = (moe["attn"]["wkv_b"].shape[-1] // nh
                         - (dqk - got["rope_head_dim"]))
    s = dict(PUBLISHED)
    if model["d_model"] == PUBLISHED["d_model"]:
        off = {k: (PUBLISHED[k], v) for k, v in got.items()
               if PUBLISHED[k] != v}
        assert not off, f"program differs from the published model: {off}"
        assert model["reg_gamma"] == PUBLISHED["balance_alpha"]
    s.update(got)
    s["balance_alpha"] = model["reg_gamma"]
    return s


def mla(p, h, m, s, mm):
    b, t, _ = h.shape
    nh, dqk = m["n_heads"], m["head_dim"]
    r, dr, dv = s["kv_lora_rank"], s["rope_head_dim"], s["v_head_dim"]
    dn = dqk - dr
    q = mm("bsd,dq->bsq", h, p["wq"]).reshape(b, t, nh, dqk)
    kv = mm("bsd,dr->bsr", h, p["wkv_a"])
    c = rms_norm(kv[..., :r], p["kv_norm"]["scale"], m["norm_eps"])
    k_pe = rope(kv[..., None, r:], m["rope_theta"])
    kvb = mm("bsr,rq->bsq", c, p["wkv_b"]).reshape(b, t, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m["rope_theta"])], -1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe, (b, t, nh, dr))], -1)
    v = kvb[..., dn:]
    nb = -(-t // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, nb * QUERY_BLOCK - t), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, nb, QUERY_BLOCK, nh, dqk), 1, 0)

    @jax.checkpoint
    def block(args):
        qi, i = args
        sc = mm("bqhd,bkhd->bhqk", qi, k) * dqk ** -0.5
        pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        sc = jnp.where(pos[:, None] >= jnp.arange(t)[None, :], sc, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, nb * QUERY_BLOCK, nh * dv)[:, :t]
    return mm("bsq,qd->bsd", o, p["wo"])


def glu(x, w1, w3, w2, mm):
    return mm("nf,fd->nd", jax.nn.silu(mm("nd,df->nf", x, w1))
              * mm("nd,df->nf", x, w3), w2)


def moe(p, x, m, s, mm):
    """y, the balance loss and the expert loads of one MoE layer."""
    b, t, d = x.shape
    xf = x.reshape(-1, d)
    n_r, k = s["router"], m["k"]
    sc = jax.nn.sigmoid(mm("nd,de->ne", xf, p["router"]))
    bias = jax.lax.stop_gradient(p["router_bias"]["bias"])
    _, idx = jax.lax.top_k(sc + bias, k)
    sel = jnp.take_along_axis(sc, idx, axis=-1)
    w = sel / (jnp.sum(sel, -1, keepdims=True) + 1e-9) * s["routed_scale"]
    chosen = jax.nn.one_hot(idx, n_r)                         # (N, k, E)
    gate = jnp.einsum("nk,nke->ne", w, chosen)[:, :m["n_experts"]]

    @jax.checkpoint
    def expert(y, e):
        w1, w3, w2, g = e
        return y + g[:, None] * glu(xf, w1, w3, w2, mm), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xf),
                        (p["we1"], p["we1g"], p["we2"], gate.T))
    for j in range(p["shared_w1"].shape[0]):
        y = y + glu(xf, p["shared_w1"][j], p["shared_w1g"][j],
                    p["shared_w2"][j], mm)
    picked = jnp.sum(chosen, axis=1).reshape(b, t, n_r)
    f = jnp.sum(picked, axis=1) * n_r / (k * t)
    pn = sc.reshape(b, t, n_r)
    pn = jnp.mean(pn / jnp.sum(pn, -1, keepdims=True), axis=1)
    balance = jnp.mean(jnp.sum(f * pn, -1))
    load = jax.lax.stop_gradient(jnp.sum(picked, axis=(0, 1)))
    return y.reshape(b, t, d), balance, load


def forward_loss(params, tokens, m, s, precision):
    """Mean next-token cross entropy + alpha * the balance losses; the MoE
    layers' loads (layers, router)."""
    mm, q = _mm(precision), _carry(precision)
    eps, vocab = m["norm_eps"], m["vocab_size"]
    x = q(params["emb"][tokens].astype(jnp.float32))
    segs = params["stack"]["segments"]

    def attend(lp, x):
        return q(x + mla(lp["attn"], rms_norm(x, lp["norm1"]["scale"], eps),
                         m, s, mm))

    @jax.checkpoint
    def dense_layer(x, lp):
        x = attend(lp, x)
        h = rms_norm(x, lp["norm2"]["scale"], eps)
        f = lp["ffn"]
        y = glu(h.reshape(-1, h.shape[-1]), f["w1"], f["w3"], f["w2"], mm)
        return q(x + y.reshape(x.shape)), None

    @jax.checkpoint
    def moe_layer(x, lp):
        x = attend(lp, x)
        y, balance, load = moe(lp["ffn"], rms_norm(x, lp["norm2"]["scale"],
                                                    eps), m, s, mm)
        return q(x + y), (balance, load)

    x, _ = jax.lax.scan(dense_layer, x, segs[0]["e0"])
    x, (balances, loads) = jax.lax.scan(moe_layer, x, segs[1]["e0"])
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    logits = mm("bsd,dv->bsv", x[:, :-1], params["unembed"][:, :vocab])
    labels = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - gold) + s["balance_alpha"] * jnp.sum(balances)
    return loss, loads


def _lr(opt, step):
    t = min(max(step / max(opt["total_steps"] - opt.get("warmup_steps", 0),
                           1), 0.0), 1.0)
    fin = opt.get("final_lr_ratio", 0.0)
    return opt["lr"] * (fin + (1 - fin) * 0.5 * (1 + math.cos(math.pi * t)))


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree_util.tree_leaves(tree)])


def train_reference(model, train, shapes, batches, seed, init_std=None,
                    precision=None):
    """Follow len(batches) training steps from the seeded weights. Returns
    the steps' losses, the per-leaf norms of the first clipped gradient and
    of the parameters' change over all steps (leaves in the order of
    ``jax.tree_util.tree_leaves`` of the parameter tree); ``first_mems`` is
    None (no XL memory)."""
    from chipbench.model import make_weights

    opt = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0,
           "grad_clip": 0.25, **train["optimizer"]}
    m, s = model, settings(model, shapes)

    def fresh():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      make_weights(shapes, seed, init_std))

    with jax.default_matmul_precision("highest"):
        params = fresh()
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t: forward_loss(p, t, m, s, precision), has_aux=True))

        def update(params, grads, mu, nu, loads, step, lr):
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree_util.tree_leaves(grads)))
            scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            c1 = 1.0 - opt["b1"] ** step
            c2 = 1.0 - opt["b2"] ** step
            mu = jax.tree_util.tree_map(
                lambda a, g: opt["b1"] * a + (1 - opt["b1"]) * g, mu, grads)
            nu = jax.tree_util.tree_map(
                lambda a, g: opt["b2"] * a + (1 - opt["b2"]) * g * g, nu,
                grads)
            new = jax.tree_util.tree_map(
                lambda p, a, v: p - lr * ((a / c1) / (jnp.sqrt(v / c2)
                                                      + opt["eps"])
                                          + opt["weight_decay"] * p),
                params, mu, nu)
            ffn = new["stack"]["segments"][1]["e0"]["ffn"]
            bias = params["stack"]["segments"][1]["e0"]["ffn"]["router_bias"]
            ffn["router_bias"] = {"bias": bias["bias"] + s["bias_rate"]
                                  * jnp.sign(jnp.mean(loads, -1, keepdims=True)
                                             - loads)}
            return new, mu, nu, _leaf_norms(grads)

        update = jax.jit(update, donate_argnums=(0, 2, 3))
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i in range(batches.shape[0]):
            (loss, loads), grads = grad_fn(params, jnp.asarray(batches[i]))
            params, mu, nu, norms = update(params, grads, mu, nu, loads,
                                           float(i + 1), _lr(opt, i))
            del grads
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = np.asarray(norms)
        del mu, nu
        change = jax.jit(lambda a, b: _leaf_norms(
            jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
        update_norms = np.asarray(change(params, fresh()))
    return {"losses": losses, "first_mems": None, "grad_norms": grad_norms,
            "update_norms": update_norms}
