"""Plain float32 reference of the paper's sigma-MoE Transformer-XL
(wt103-262m-moe, Csordas et al. 2023, Tab. 8/9) and of its training step.

Straightforward ``jax.numpy`` with every matrix product at ``highest``
precision; no kernels, no routing plan, no cache. It reads the sizes from the
configuration file's ``model`` section and the optimizer from its ``train``
section, and makes the weights from the seed with the harness's generator,
laid out as the program's checkpoint is (``emb``, ``unembed``,
``final_norm``, and the stacked layer ``stack/segments/0/e0``).

Layer (pre-norm): h = LN1(x); x += XLAttn(h, memory); x += MoE(LN2(x)).
XLAttn: keys and values over [memory; h] (memory without gradient), scores
(q + u).k + (q + v).R[i - j] with R[t] = sinusoid(t) W_r for the distance t
between query i and key j, causal over the memory and the segment, scaled by
head_dim**-0.5. The memory a layer leaves for the next step is its last
``xl_memory`` rows of [memory; h]. sigma-MoE: sel = sigmoid(x W3), the top-k
experts by sel, y = sum_e sel[e] W2_e relu(W1_e x), every expert computed
densely and masked; loss adds reg_gamma * sum(p log p) per layer, p the
batch mean of softmax(x W3). Loss: mean next-token cross entropy over the real
vocabulary. Step: gradient, clip to the global norm, AdamW with the cosine
schedule; three steps carry the memory from one to the next.

``precision="fp8"`` is the control that the check has to fail: the same
model computed in float8 e4m3, the precision below the program's bfloat16,
as the program computes in bfloat16. Every product's operands and result,
the embedding and the residual stream after each sublayer are rounded to
float8 (per-tensor scale), and so is each of their gradients on the way
back, so that the backward products take float8 operands too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 240.0     # largest finite value of 4 exponent and 3 mantissa bits


def _round8(x):
    """x rounded to 4 exponent and 3 mantissa bits (float8 e4m3) under a
    per-tensor scale, in float32. ``reduce_precision`` and not a round trip
    through ``float8_e4m3fn``: XLA may drop a convert pair as excess
    precision, and on the TPU it dropped some."""
    scale = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


@jax.custom_vjp
def _fp8(x):
    return _round8(x)


_fp8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _carry(precision):
    """What an activation is rounded to between operations."""
    return _fp8 if precision == "fp8" else (lambda x: x)


def _mm(precision):
    q = _carry(precision)

    def mm(spec, a, b):
        return q(jnp.einsum(spec, q(a), q(b)))
    return mm


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def sinusoid(t, d):
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = t.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def by_key(bd_t):
    """(..., S, T) scores indexed by distance t -> indexed by key j, where
    query i sits at position T - S + i and its distance to key j is
    T - S + i - j: out[..., i, j] = bd_t[..., i, T - S + i - j] wherever
    that distance is >= 0 (elsewhere 0; causality masks it). Row i is the
    distance-reversed row read from offset S - 1 - i: one window per query,
    no gather of single elements."""
    s, t = bd_t.shape[-2], bd_t.shape[-1]
    rev = jnp.pad(bd_t[..., ::-1], [(0, 0)] * (bd_t.ndim - 1) + [(0, s)])
    rows = jnp.moveaxis(rev, -2, 0)
    out = jax.vmap(lambda row, o: jax.lax.dynamic_slice_in_dim(
        row, o, t, axis=-1))(rows, (s - 1) - jnp.arange(s))
    return jnp.moveaxis(out, 0, -2)


def xl_attention(p, h, mem, m, mm):
    b, s, d = h.shape
    nh, dh = m["n_heads"], m["head_dim"]
    src = jnp.concatenate([jax.lax.stop_gradient(mem), h], axis=1)
    t = src.shape[1]
    q = mm("bsd,dq->bsq", h, p["wq"]).reshape(b, s, nh, dh)
    k = mm("btd,dq->btq", src, p["wk"]).reshape(b, t, nh, dh)
    v = mm("btd,dq->btq", src, p["wv"]).reshape(b, t, nh, dh)
    r = mm("td,dq->tq", sinusoid(jnp.arange(t), d),
           p["w_r"]).reshape(t, nh, dh)
    ac = mm("bihd,bjhd->bhij", q + p["u_bias"], k)
    bd_t = mm("bihd,thd->bhit", q + p["v_bias"], r)
    causal = ((t - s) + jnp.arange(s))[:, None] >= jnp.arange(t)[None, :]
    sc = jnp.where(causal, (ac + by_key(bd_t)) * dh ** -0.5, -jnp.inf)
    att = jax.nn.softmax(sc, axis=-1)
    o = mm("bhij,bjhd->bihd", att, v).reshape(b, s, nh * dh)
    return mm("bsq,qd->bsd", o, p["wo"])


def sigma_moe(p, x, m, mm):
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    logits = mm("nd,de->ne", xf, p["router"])
    sel = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(sel, m["k"])
    chosen = jax.lax.stop_gradient(
        jnp.sum(jax.nn.one_hot(idx, m["n_experts"]), axis=1))
    gate = sel * chosen                                        # (N, E)
    h = jax.nn.relu(mm("nd,edg->neg", xf, p["we1"]))
    y = mm("neg,egd->ned", h, p["we2"])
    y = jnp.sum(y * gate[..., None], axis=1)
    pm = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    reg = m["reg_gamma"] * jnp.sum(pm * jnp.log(pm + 1e-9))
    return y.reshape(*lead, -1), reg


def forward_loss(params, tokens, mems, m, precision):
    """Mean next-token cross entropy + MoE regularizer; new memories."""
    mm, q = _mm(precision), _carry(precision)
    eps, vocab = m["norm_eps"], m["vocab_size"]
    x = q(params["emb"][tokens].astype(jnp.float32))
    layers = params["stack"]["segments"][0]["e0"]

    @jax.checkpoint
    def layer(x, xs):
        lp, mem = xs
        h = layer_norm(x, lp["norm1"]["scale"], lp["norm1"]["bias"], eps)
        new_mem = jnp.concatenate([mem, h], axis=1)[:, -mem.shape[1]:]
        x = q(x + xl_attention(lp["attn"], h, mem, m, mm))
        h2 = layer_norm(x, lp["norm2"]["scale"], lp["norm2"]["bias"], eps)
        y, reg = sigma_moe(lp["ffn"], h2, m, mm)
        return q(x + y), (jax.lax.stop_gradient(new_mem), reg)

    x, (new_mems, regs) = jax.lax.scan(layer, x, (layers, mems))
    x = layer_norm(x, params["final_norm"]["scale"],
                   params["final_norm"]["bias"], eps)
    logits = mm("bsd,dv->bsv", x[:, :-1], params["unembed"][:, :vocab])
    labels = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + jnp.sum(regs), new_mems


def _lr(opt, step):
    t = min(max(step / max(opt["total_steps"] - opt.get("warmup_steps", 0),
                           1), 0.0), 1.0)
    fin = opt.get("final_lr_ratio", 0.0)
    return opt["lr"] * (fin + (1 - fin) * 0.5 * (1 + math.cos(math.pi * t)))


def train_reference(model, train, shapes, batches, seed, init_std=None,
                    precision="f32"):
    """Follow len(batches) training steps from the seeded weights. Returns
    the steps' losses, the memory the first step leaves (``first_mems``,
    (layers, batch, xl_memory, d_model)), the per-leaf norms of the first
    clipped gradient and of the parameters' change over all steps (leaves in
    the order of ``jax.tree_util.tree_leaves`` of the parameter tree)."""
    from chipbench.model import make_weights

    opt = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0,
           "grad_clip": 0.25, **train["optimizer"]}
    m = model
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        make_weights(shapes, seed, init_std))
        p0 = params
        n_layers, mlen = m["n_layers"], m["xl_memory"]
        b = batches.shape[1]
        mems = jnp.zeros((n_layers, b, mlen, m["d_model"]), jnp.float32)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t, mem: forward_loss(p, t, mem, m, precision),
            has_aux=True))

        @jax.jit
        def update(params, grads, mu, nu, step, lr):
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
            params = jax.tree_util.tree_map(
                lambda p, a, v: p - lr * ((a / c1) / (jnp.sqrt(v / c2)
                                                      + opt["eps"])
                                          + opt["weight_decay"] * p),
                params, mu, nu)
            norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g)))
                               for g in jax.tree_util.tree_leaves(grads)])
            return params, mu, nu, norms

        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, grad_norms, first_mems = [], None, None
        for i in range(batches.shape[0]):
            (loss, mems), grads = grad_fn(params, jnp.asarray(batches[i]),
                                          mems)
            params, mu, nu, norms = update(params, grads, mu, nu,
                                           float(i + 1), _lr(opt, i))
            losses.append(float(loss))
            if grad_norms is None:
                first_mems = np.asarray(mems)
                grad_norms = np.asarray(norms)
            del grads
        change = jax.jit(lambda a, b: jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in
            zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]))
        update_norms = np.asarray(change(params, p0))
    return {"losses": losses, "first_mems": first_mems,
            "grad_norms": grad_norms, "update_norms": update_norms}
