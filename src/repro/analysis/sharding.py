"""Pass 4: sharding-table analyzer — PARAM_AXES x rule sets x mesh layouts.

The seed shipped a layout bug that only surfaced at mesh setup on a real
``--ffn pkm`` run: the PKM key tables ruled two positional dims onto the
'model' mesh axis and every sharded run crashed in NamedSharding
construction. ``strict_duplicate_check`` turned that class of bug into a
test failure — but only for the (model, mesh, rules) combinations a test
happens to build. This pass is the full offline closure of that check:

  table structure   every ``PARAM_AXES`` entry's axes tuple has exactly its
                    declared rank
  rule coverage     every logical axis the table uses has an explicit entry
                    in every rule set that can meet it (an absent key
                    silently replicates — each intentional replication must
                    be spelled out as ``None`` in the table, not implied)
  duplicate sweep   every table entry — at its own rank AND the scan-stacked
                    rank(+1) / superblock rank(+2) fallbacks — resolves under
                    strict mode for every rule set x every mesh axis layout
                    in ``launch.mesh.MESH_AXIS_LAYOUTS``
  model closure     every parameter leaf of every registry model variant
                    (sigma_moe / pkm / topk FFNs, real scan-stacked
                    ``eval_shape`` trees) reaches a PARAM_AXES rule — a leaf
                    falling through to the ``(None,) * rank`` fallback ships
                    fully replicated with nobody having decided that — and
                    its strict spec resolves under every rule set x layout
  pod_err closure   pod-stacked error-feedback wrapping (``{"err": ...}``
                    subtrees with a leading per-pod dim) shards its leading
                    dim over 'pod' for every leaf whose base layout is ruled

Meshes are built with every axis at size 1, so the sweep runs on any single
device; duplicate detection only depends on axis NAMES, never sizes.
"""
from __future__ import annotations

from typing import List, Tuple

from .report import Finding


def _bad(check: str, location: str, detail: str) -> Finding:
    return Finding("sharding", check, location, detail)


# Logical axes that only serving-state leaves (KV caches) carry; TRAIN/SP
# rule sets never meet them, so they are exempt from train-side coverage.
_SERVE_ONLY_AXES = ("kv_seq",)

_MODEL_FFNS = ("sigma_moe", "pkm", "topk")


def _rule_sets():
    from ..sharding import logical as L
    return (
        ("train", L.TRAIN_RULES),
        ("serve", L.SERVE_RULES),
        ("sp", L.SP_RULES),
        # context-parallel decode variant: kv heads not divisible by TP
        ("serve_ctx", L.serve_rules_for(8, 3)),
    )


def _meshes():
    from ..launch.mesh import MESH_AXIS_LAYOUTS, make_mesh
    return [(ax, make_mesh((1,) * len(ax), ax))
            for ax in MESH_AXIS_LAYOUTS]


def _check_table() -> Tuple[List[Finding], int]:
    from ..sharding import logical as L

    findings: List[Finding] = []
    checks = 0
    rule_sets = _rule_sets()
    meshes = _meshes()

    used_axes = sorted({a for axes in L.PARAM_AXES.values()
                        for a in axes if a is not None}
                       | {"layers", "pod_err", "batch", "seq"})
    for rname, rules in rule_sets:
        for ax in used_axes:
            checks += 1
            if ax in _SERVE_ONLY_AXES and rname in ("train", "sp"):
                continue
            if ax not in rules:
                findings.append(_bad(
                    "rule-coverage", f"{rname}[{ax!r}]",
                    f"logical axis {ax!r} is used by PARAM_AXES but has no "
                    f"entry in the {rname} rules — it replicates silently; "
                    f"spell intentional replication as an explicit None"))

    for (name, rank), axes in sorted(L.PARAM_AXES.items()):
        checks += 1
        if len(axes) != rank:
            findings.append(_bad(
                "rank-mismatch", f"PARAM_AXES[({name!r}, {rank})]",
                f"axes tuple {axes} has {len(axes)} entries for declared "
                f"rank {rank}"))
            continue
        # the entry itself, plus the scan-stacked and superblock fallbacks
        # _leaf_axes can derive from it
        variants = ((rank, axes),
                    (rank + 1, ("layers",) + axes),
                    (rank + 2, ("layers", "layers") + axes))
        for vrank, vaxes in variants:
            for rname, rules in rule_sets:
                for mesh_axes, mesh in meshes:
                    checks += 1
                    try:
                        L.spec_for_axes(vaxes, rules, mesh, strict=True,
                                        path=name)
                    except L.DuplicateMeshAxisError as e:
                        findings.append(_bad(
                            "duplicate-axis",
                            f"{name}[rank {vrank}] {rname} "
                            f"mesh={'x'.join(mesh_axes)}",
                            str(e)))
    return findings, checks


def _model_trees():
    """(variant name, scan-stacked eval_shape param tree) per registry FFN."""
    import jax
    from ..configs.archs import reduced
    from ..models.registry import build_model

    out = []
    for kind in _MODEL_FFNS:
        model = build_model(reduced("wt103-47m-moe"), ffn=kind)
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        out.append((kind, tree))
    return out


def _check_models() -> Tuple[List[Finding], int]:
    import jax
    from ..sharding import logical as L

    findings: List[Finding] = []
    checks = 0
    rule_sets = _rule_sets()
    meshes = _meshes()
    pod_mesh = next((m for ax, m in meshes if "pod" in ax), None)

    for kind, tree in _model_trees():
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in leaves:
            keys = L._path_keys(path)
            name, rank = (keys[-1] if keys else ""), leaf.ndim
            loc = f"{kind}:{jax.tree_util.keystr(path)}"
            checks += 1
            if rank and not any((name, rank - d) in L.PARAM_AXES
                                for d in (0, 1, 2)):
                findings.append(_bad(
                    "unruled-leaf", loc,
                    f"leaf {name!r} (rank {rank}) reaches no PARAM_AXES "
                    f"entry — it would ship fully replicated through the "
                    f"(None,)*rank fallback without anyone deciding that"))
                continue
            for rname, rules in rule_sets:
                for mesh_axes, mesh in meshes:
                    checks += 1
                    try:
                        L.spec_for(path, leaf, rules, mesh, strict=True)
                    except L.DuplicateMeshAxisError as e:
                        findings.append(_bad(
                            "duplicate-axis",
                            f"{loc} {rname} mesh={'x'.join(mesh_axes)}",
                            str(e)))

        # pod-stacked error-feedback wrapping: {"err": tree} with a leading
        # per-pod dim must shard that dim over 'pod' wherever the base
        # layout is ruled (optim/compress stores one residual per pod).
        if pod_mesh is None:
            continue
        wrapped = {"err": jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype), tree)}
        for path, leaf in jax.tree_util.tree_flatten_with_path(wrapped)[0]:
            keys = L._path_keys(path)
            name = keys[-1] if keys else ""
            inner = L._leaf_axes(name, leaf.ndim - 1)
            if not any(a is not None for a in inner):
                continue
            checks += 1
            try:
                spec = L.spec_for(path, leaf, L.TRAIN_RULES, pod_mesh,
                                  strict=True)
            except L.DuplicateMeshAxisError as e:
                findings.append(_bad(
                    "duplicate-axis", f"{kind}:err{jax.tree_util.keystr(path)}",
                    str(e)))
                continue
            lead = tuple(spec)[0] if len(tuple(spec)) else None
            if lead != "pod":
                findings.append(_bad(
                    "pod-err", f"{kind}:{jax.tree_util.keystr(path)}",
                    f"pod-stacked error-feedback leaf {name!r} shards its "
                    f"leading per-pod dim as {lead!r}, expected 'pod' — "
                    f"every pod would store every pod's residual"))
    return findings, checks


def check_sharding() -> Tuple[List[Finding], int]:
    f1, c1 = _check_table()
    f2, c2 = _check_models()
    return f1 + f2, c1 + c2
