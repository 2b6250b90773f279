"""Jaxpr-level stage auditor: what the registered serving stages COMPILE to.

The AST layers (``lint.py`` + ``callgraph.py``) reason about source; this
layer reasons about the artifact.  Every jitted stage a scheduler /
multipool / cluster registers through ``audit_stages()`` is traced
abstractly — ``jax.make_jaxpr`` on ``ShapeDtypeStruct`` arguments, no
device execution — and the resulting jaxprs are walked for hazards the
serving invariants assume away:

* **JXP001** — a callback primitive (``debug_callback`` /
  ``debug_print`` / ``pure_callback`` / ``io_callback``) compiled into a
  stage: a host round-trip per dispatch that the transfer guard cannot
  see.
* **JXP002** — a ``device_put`` primitive inside a stage: a host upload
  smuggled into the traced graph instead of going through the scheduler's
  cached explicit-upload paths (``_chunk_t0`` / ``_thr_device``).
* **JXP003** — a constant above ``LARGE_CONST_ELEMS`` elements folded
  into the jaxpr: a closure-captured device array, proven at the compiled
  level (the TRC006 hazard without the syntactic guesswork).
* **JXP004** — the stage returns its cache pytree with different leaf
  dtypes than it received: silent ``convert_element_type`` on the cache
  path, the exact drift class that breaks paged/contiguous and
  spec/target bit-parity.
* **JXP005** — a donated argument has a leaf no output can alias
  (shape/dtype multiset mismatch), so the donation silently degrades to
  a copy.

``audit_serving_stack()`` builds a representative stack — a two-tier
speculative cluster plus a standalone paged+prefix-cache scheduler, both
on the smoke arch — audits every registered stage, and hands the traced
jaxprs on to the cost cross-check (``costcheck.py``).  Findings report
through the ordinary ``Finding`` / baseline gate under stable
pseudo-paths (``<jaxpr:device/prefill>``), so the committed
zero-findings baseline covers this layer too.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.report import Finding
from repro.analysis.rules import RULES

# a folded-in constant this large is a captured table/cache, not an iota:
# the repo's legitimate stage consts (position iotas, exit one-hots) are
# O(max_len) ~ a few hundred elements
LARGE_CONST_ELEMS = 16384

# primitives that call back into the host per dispatch
_CALLBACK_PRIMS = ("debug_callback", "debug_print", "pure_callback",
                   "io_callback", "callback")


def _finding(rule: str, path: str, message: str, snippet: str) -> Finding:
    r = RULES[rule]
    return Finding(rule=rule, path=path, line=0, col=0,
                   severity=r.severity, message=message, snippet=snippet)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _sub_jaxprs(params: Dict[str, Any]) -> Iterable[Tuple[Any, int]]:
    """(closed_or_open_jaxpr, multiplicity) pairs nested in eqn params.

    Multiplicity is how many times the sub-jaxpr's body executes per
    outer dispatch — ``scan`` bodies run ``length`` times; ``cond``
    branches are alternatives (cost handled separately), everything else
    runs once.
    """
    mult = int(params.get("length", 1)) if "length" in params else 1
    for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr"):
        sub = params.get(key)
        if sub is not None:
            yield sub, mult
    for br in params.get("branches", ()) or ():
        yield br, 1


def iter_eqns(jaxpr: Any) -> Iterable[Any]:
    """Every equation of ``jaxpr`` and all nested sub-jaxprs (pjit bodies,
    scan/while bodies, cond branches)."""
    closed = getattr(jaxpr, "jaxpr", None)
    open_jaxpr = closed if closed is not None else jaxpr
    for eqn in open_jaxpr.eqns:
        yield eqn
        for sub, _ in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _iter_consts(jaxpr: Any) -> Iterable[Any]:
    """Constants captured by ``jaxpr`` or any nested sub-jaxpr."""
    closed = getattr(jaxpr, "jaxpr", None)
    if closed is not None:
        yield from jaxpr.consts
        open_jaxpr = closed
    else:
        open_jaxpr = jaxpr
    for eqn in open_jaxpr.eqns:
        for sub, _ in _sub_jaxprs(eqn.params):
            yield from _iter_consts(sub)


def _leaf_specs(tree: Any) -> List[Tuple[Tuple[int, ...], Any]]:
    """(shape, dtype) per leaf, via the aval duck-type."""
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        out.append((tuple(jnp.shape(leaf)), jnp.result_type(leaf)))
    return out


def _leaf_dtypes(tree: Any) -> List[Any]:
    return [jnp.result_type(leaf)
            for leaf in jax.tree_util.tree_leaves(tree)]


# ---------------------------------------------------------------------------
# per-stage audit
# ---------------------------------------------------------------------------
def audit_stage(spec: Any, path: str) -> Tuple[List[Finding], Any]:
    """Audit one registered stage; returns (findings, closed jaxpr).

    ``spec`` is a ``repro.serving.scheduler.StageSpec``; ``path`` the
    stable pseudo-path findings are keyed under.
    """
    findings: List[Finding] = []
    jaxpr = jax.make_jaxpr(spec.fn)(*spec.args)

    # JXP001 / JXP002: primitives that touch the host
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in _CALLBACK_PRIMS:
            findings.append(_finding(
                "JXP001", path,
                f"stage '{spec.name}' compiles a '{prim}' primitive: a "
                "host round-trip on every dispatch",
                f"{spec.name}:{prim}"))
        elif prim == "device_put":
            findings.append(_finding(
                "JXP002", path,
                f"stage '{spec.name}' compiles a device_put: a host value "
                "is uploaded inside the traced graph",
                f"{spec.name}:{prim}"))

    # JXP003: closure-captured constants folded into the compiled stage
    for const in _iter_consts(jaxpr):
        shape = tuple(jnp.shape(const))
        elems = 1
        for d in shape:
            elems *= int(d)
        if elems >= LARGE_CONST_ELEMS:
            findings.append(_finding(
                "JXP003", path,
                f"stage '{spec.name}' folds a {shape} "
                f"{jnp.result_type(const)} constant ({elems} elements) "
                "into its jaxpr — a closure-captured array; pass it as an "
                "argument",
                f"{spec.name}:const{shape}"))

    out_shape = jax.eval_shape(spec.fn, *spec.args)

    # JXP004: cache dtype round-trip
    if spec.cache_in is not None and spec.cache_out is not None:
        din = _leaf_dtypes(spec.args[spec.cache_in])
        dout = _leaf_dtypes(spec.cache_out(out_shape))
        if din != dout:
            drift = sorted({f"{a}->{b}" for a, b in zip(din, dout)
                            if a != b}) if len(din) == len(dout) \
                else [f"{len(din)} leaves in, {len(dout)} out"]
            findings.append(_finding(
                "JXP004", path,
                f"stage '{spec.name}' returns its cache with drifted leaf "
                f"dtypes ({', '.join(drift)}): bit-parity across "
                "paged/contiguous and spec/target paths is broken",
                f"{spec.name}:cache-dtype"))

    # JXP005: every donated leaf must have an output it can alias
    if spec.donate_argnums:
        avail = _leaf_specs(out_shape)
        for argnum in spec.donate_argnums:
            for leaf_spec in _leaf_specs(spec.args[argnum]):
                if leaf_spec in avail:
                    avail.remove(leaf_spec)
                else:
                    shape, dt = leaf_spec
                    findings.append(_finding(
                        "JXP005", path,
                        f"stage '{spec.name}' donates argument {argnum} "
                        f"but its {shape} {dt} leaf matches no remaining "
                        "output buffer — the donation degrades to a copy",
                        f"{spec.name}:donate{argnum}"))
    return findings, jaxpr


def audit_registry(stages: Dict[str, Any], prefix: str
                   ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Audit a flat ``name -> StageSpec`` registry; jaxprs keyed by name."""
    findings: List[Finding] = []
    jaxprs: Dict[str, Any] = {}
    for name, spec in sorted(stages.items()):
        path = f"<jaxpr:{prefix}/{name}>"
        f, jx = audit_stage(spec, path)
        findings.extend(f)
        jaxprs[name] = jx
    return findings, jaxprs


# ---------------------------------------------------------------------------
# the audited stack
# ---------------------------------------------------------------------------
def build_audit_stack() -> Dict[str, Any]:
    """Representative serving stack for the audit, smoke-arch runtime:

    * a two-tier ``TieredServingCluster`` (device + cloud) with the
      speculative draft/target bridge forced into existence — covers the
      single-model tier arenas, the multipool flattening, and both spec
      bridge arenas (propose/verify included);
    * a standalone paged + prefix-cache ``ContinuousBatchScheduler`` —
      covers the paged stage variants the cluster default doesn't build.

    Returns ``name -> object exposing audit_stages()`` plus the model
    handle under ``"_model"`` for the cost cross-check.
    """
    from repro.configs import get_config
    from repro.models import Model
    from repro.serving import (ClusterConfig, ContinuousBatchScheduler,
                               ModelGroup, SchedulerConfig,
                               TieredServingCluster)

    cfg = get_config("granite-3-2b-smoke")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    cluster = TieredServingCluster(
        ModelGroup([("draft", model, params), ("target", model, params)]),
        plan_cfg={"draft": get_config("granite-3-2b"),
                  "target": get_config("deepseek-v3-671b")},
        cfg=ClusterConfig(base_slots=2, max_len=32, prefill_chunk=8,
                          spec_draft="draft", spec_k=4))
    cluster._spec_pair("target")       # force the lazy spec bridge to build

    paged = ContinuousBatchScheduler(
        model, params,
        SchedulerConfig(n_slots=2, max_len=32, prefill_chunk=8,
                        paged=True, page_size=16, prefix_cache=True))
    return {"cluster": cluster, "paged": paged, "_model": model}


def _flatten_registries(stack: Dict[str, Any]
                        ) -> Dict[str, Dict[str, Any]]:
    """``prefix -> flat stage registry`` over the audit stack."""
    out: Dict[str, Dict[str, Any]] = {}
    for name, obj in stack.items():
        if name.startswith("_"):
            continue
        stages = obj.audit_stages()
        if stages and all(isinstance(v, dict) for v in stages.values()):
            for sub, reg in stages.items():      # cluster: tier -> registry
                out[f"{name}/{sub}"] = reg
        else:
            out[name] = stages
    return out


def audit_serving_stack(stack: Optional[Dict[str, Any]] = None
                        ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Audit every registered stage of the (default) audit stack.

    Returns ``(findings, context)`` where context carries the per-registry
    jaxprs and the runtime model for ``costcheck``.
    """
    if stack is None:
        stack = build_audit_stack()
    findings: List[Finding] = []
    jaxprs: Dict[str, Dict[str, Any]] = {}
    for prefix, registry in sorted(_flatten_registries(stack).items()):
        f, jx = audit_registry(registry, prefix)
        findings.extend(f)
        jaxprs[prefix] = jx
    n_stages = sum(len(v) for v in jaxprs.values())
    context = {"jaxprs": jaxprs, "model": stack.get("_model"),
               "stack": stack, "n_stages": n_stages}
    return findings, context
