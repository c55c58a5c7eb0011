"""The program's model configuration for a cell, checked against the
configuration file, and the weights the harness makes for it.

A configuration file's ``model`` section states every size and setting the
cell runs with; ``program_config`` builds the registered arch with the file's
overrides and refuses to run when any stated value differs from what the
program would run. A program change that alters the model therefore cannot
pass unnoticed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

# configuration-file key -> attribute path in repro's ModelConfig
FIELDS = {
    "n_layers": "n_layers", "d_model": "d_model", "vocab_size": "vocab_size",
    "norm": "norm", "norm_eps": "norm_eps", "tie_embeddings": "tie_embeddings",
    "pos_encoding": "pos_encoding", "xl_memory": "xl_memory",
    "dropout": "dropout", "dtype": "dtype", "param_dtype": "param_dtype",
    "logit_softcap": "logit_softcap",
    "n_heads": "attention.n_heads", "n_kv_heads": "attention.n_kv_heads",
    "head_dim": "attention.head_dim", "rope_theta": "attention.rope_theta",
    "attention_kind": "attention.kind",
    "ffn_kind": "ffn.kind", "n_experts": "ffn.n_experts",
    "expert_size": "ffn.expert_size", "k": "ffn.k",
    "activation": "ffn.activation",
    "selector_activation": "ffn.selector_activation",
    "renormalize": "ffn.renormalize", "glu_experts": "ffn.glu_experts",
    "n_shared_experts": "ffn.n_shared_experts",
    "expert_dropout": "ffn.expert_dropout", "reg_kind": "ffn.reg_kind",
    "reg_gamma": "ffn.reg_gamma", "dispatch": "ffn.dispatch",
}


def _get(cfg, path: str):
    for attr in path.split("."):
        cfg = getattr(cfg, attr)
    return cfg


def stated(cfg) -> Dict[str, Any]:
    """Every FIELDS value of a program config, as a configuration file
    would state it."""
    return {k: _get(cfg, p) for k, p in FIELDS.items()}


class ConfigMismatch(ValueError):
    pass


def check_stated(model: Dict[str, Any], cfg) -> None:
    """Raise when a value the configuration file states differs from what
    the program's config holds."""
    unknown = sorted(set(model) - set(FIELDS))
    if unknown:
        raise ConfigMismatch(f"configuration states unknown keys {unknown}")
    off = {k: (v, _get(cfg, FIELDS[k])) for k, v in model.items()
           if _get(cfg, FIELDS[k]) != v}
    if off:
        raise ConfigMismatch("configuration file and program differ "
                             "(stated, program): " + repr(off))


def program_config(run) -> Any:
    """The registered arch with the file's overrides, checked against the
    file. Under a CPU rehearsal (``run.test['reduced']``) the arch's reduced
    config stands in, and the file's widths are not compared."""
    from repro.configs import get_config, reduced
    c = run.config
    arch = c["arch"]
    cfg = reduced(arch) if run.test.get("reduced") else get_config(arch)
    cfg = cfg.override(**c.get("overrides", {}))
    ffn = dict(c.get("ffn_overrides", {}))
    if run.test.get("impl"):
        ffn["impl"] = run.test["impl"]
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn, **ffn))
    if run.test.get("reduced"):
        widths = {"n_layers", "d_model", "vocab_size", "n_heads",
                  "n_kv_heads", "head_dim", "xl_memory", "n_experts",
                  "expert_size", "k"}
        check_stated({k: v for k, v in c["model"].items()
                      if k not in widths}, cfg)
    else:
        check_stated(c["model"], cfg)
    return cfg


def model_dict(run, cfg) -> Dict[str, Any]:
    """The sizes the reference is given: the file's, or under a CPU
    rehearsal those of the reduced config."""
    return stated(cfg) if run.test.get("reduced") else dict(run.config["model"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _leaf_name(path) -> str:
    key = path[-1]
    return str(getattr(key, "key", getattr(key, "name", key)))


def _std(name: str, shape, init_std) -> float:
    if name in init_std:
        return init_std[name]
    if name == "emb":
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5


def make_weights(shapes, seed: int, init_std=None):
    """Weights for a parameter tree of ``ShapeDtypeStruct``s, made on the
    device in one jitted call from the seed, in each leaf's own dtype: norm
    scales 1, norm biases 0, a leaf named in ``init_std`` normal with that
    std, every other leaf normal with std fan_in**-0.5 (``emb``:
    d_model**-0.5)."""
    import jax
    import jax.numpy as jnp
    from chipbench.harness import jax_key

    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    init_std = dict(init_std or {})

    def make(key):
        out = []
        for i, (path, s) in enumerate(paths):
            name = _leaf_name(path)
            if name == "scale":
                out.append(jnp.ones(s.shape, s.dtype))
            elif name == "bias":
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                std = jnp.asarray(_std(name, s.shape, init_std), s.dtype)
                out.append(jax.random.normal(jax.random.fold_in(key, i),
                                             s.shape, s.dtype) * std)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax_key(seed, 1))
