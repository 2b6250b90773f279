"""Rule registry for the serving-stack analyzer.

Each rule has a stable id (referenced by baselines, docs and tests), a
severity, a one-line description, and — for ``--explain`` — a minimal
violating snippet plus its fix.  The ids are grouped:

* ``TRC***`` — recompile / concretization hazards inside traced code
  (jitted functions, ``lax.scan`` bodies, Pallas kernels).
* ``IPC***`` — the same hazard classes reached *interprocedurally*: taint
  flows from a traced argument through a same-module helper call chain
  (``analysis/callgraph.py``); the finding message carries the chain.
* ``PLT***`` — Pallas-specific legality and plumbing rules.
* ``JXP***`` — jaxpr-level stage-audit rules: what the registered jitted
  serving stages actually compile to (``analysis/jaxpr_audit.py``).
* ``CST***`` — cost-graph honesty: compiled-stage FLOPs vs the analytic
  per-tier costs the admission router prices with
  (``analysis/costcheck.py``).
* ``SYN***`` — host-sync hazards in the serving poll hot loop: methods of
  a polling class (``poll``/``step``/``tick``/``prefill_poll`` and the
  ``_step*``/``_poll*``/``_dispatch*``/``_commit*`` helpers) must not
  concretize jitted-stage outputs implicitly; the only legal readback is
  an explicit ``jax.device_get`` (the overlapped pipeline batches ONE per
  readback window).

``docs/invariants.md`` lists every rule with its enforced invariant and
how to run / append the committed baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    severity: str                      # "error" | "warning"
    description: str
    example: str = ""                  # minimal violating snippet
    fix: str = ""                      # how to repair it


_ALL = [
    Rule("TRC001", "traced-concretization", "error",
         "int()/float()/bool() on a traced value forces a host sync and "
         "bakes the value into the compiled graph (recompile per value)",
         example="@jax.jit\ndef f(x):\n    return int(x[0]) + 1",
         fix="keep the value on device (x[0] + 1) or mark the argument "
             "static via static_argnames if it is genuinely config"),
    Rule("TRC002", "traced-item-sync", "error",
         ".item()/.tolist() on a traced value is a blocking device->host "
         "sync inside a traced code path",
         example="@jax.jit\ndef f(x):\n    return x.sum().item()",
         fix="return the device scalar and .item() it OUTSIDE the jit, "
             "after the intended jax.device_get boundary"),
    Rule("TRC003", "traced-len", "warning",
         "len() on a traced value: static for arrays but an error on "
         "scalars, and usually feeds shape-dependent host control flow",
         example="@jax.jit\ndef f(x):\n    return x * len(x)",
         fix="use x.shape[0] — shape access is static under trace and "
             "launders the taint explicitly"),
    Rule("TRC004", "traced-control-flow", "error",
         "Python if/while/for/assert on a traced value concretizes it at "
         "trace time — use lax.cond/select/scan instead",
         example="@jax.jit\ndef f(x):\n    if x > 0:\n        x = x + 1\n"
                 "    return x",
         fix="jnp.where(x > 0, x + 1, x) for selects, lax.cond for "
             "branching compute, lax.scan/fori_loop for loops"),
    Rule("TRC005", "traced-fstring", "warning",
         "f-string formatting of a traced value concretizes it (and hides "
         "a device sync inside logging)",
         example="@jax.jit\ndef f(x):\n    print(f\"x={x}\")\n    return x",
         fix="jax.debug.print(\"x={x}\", x=x) traces a callback instead "
             "of concretizing (or log outside the jit)"),
    Rule("TRC006", "jit-closure-capture", "error",
         "device array captured in a jax.jit closure is baked in as a "
         "constant: stale values and a silent recompile when replaced",
         example="table = jnp.arange(8)\ndef lookup(i):\n    return "
                 "table[i]\nfn = jax.jit(lookup)",
         fix="pass the array as an argument: jax.jit(lambda t, i: t[i])"),
    Rule("TRC007", "host-numpy-on-traced", "error",
         "np.* call on a traced value concretizes it on host inside a "
         "traced code path",
         example="@jax.jit\ndef f(x):\n    return np.asarray(x).sum()",
         fix="use the jnp equivalent (jnp.asarray/jnp.sum) so the op "
             "stays in the traced graph"),
    Rule("IPC001", "interproc-concretization", "error",
         "a helper called from traced code concretizes / host-syncs a "
         "value that is tainted by a traced argument (int()/float()/"
         "bool()/.item()/.tolist()/np.* one or more calls deep)",
         example="@jax.jit\ndef f(x):\n    return _helper(x)\n\ndef "
                 "_helper(x):\n    return int(x)",
         fix="same repair as TRC001/TRC002/TRC007, applied inside the "
             "helper — or stop passing traced values into host-only "
             "helpers; the finding message names the full call chain"),
    Rule("IPC002", "interproc-control-flow", "error",
         "a helper called from traced code branches/loops/asserts on a "
         "value tainted by a traced argument",
         example="@jax.jit\ndef f(x):\n    return _helper(x)\n\ndef "
                 "_helper(x):\n    if x > 0:\n        return x + 1\n"
                 "    return x",
         fix="use lax.cond/jnp.where/lax.scan inside the helper (see "
             "TRC004); the finding message names the full call chain"),
    Rule("IPC003", "interproc-host-leak", "warning",
         "a helper called from traced code applies len() or f-string "
         "formatting to a value tainted by a traced argument",
         example="@jax.jit\ndef f(x):\n    return _helper(x)\n\ndef "
                 "_helper(x):\n    return x * len(x)",
         fix="use .shape[0] / jax.debug.print inside the helper (see "
             "TRC003/TRC005); the finding message names the full chain"),
    Rule("PLT001", "pallas-tile-lane", "error",
         "pl.BlockSpec/VMEM block's last dim must be a multiple of 128 "
         "(MXU/VPU lane width) or exactly 1",
         example="pl.BlockSpec((8, 100), lambda i: (i, 0))",
         fix="pad the lane dim to a multiple of 128: "
             "pl.BlockSpec((8, 128), lambda i: (i, 0))"),
    Rule("PLT002", "pallas-tile-sublane", "error",
         "pl.BlockSpec/VMEM block's second-to-last dim must be a multiple "
         "of 8 (f32 sublane; 16 for bf16, 32 for int8) or exactly 1",
         example="pl.BlockSpec((6, 128), lambda i: (i, 0))",
         fix="pad the sublane dim to a multiple of 8: "
             "pl.BlockSpec((8, 128), lambda i: (i, 0))"),
    Rule("PLT003", "pallas-missing-interpret", "error",
         "pl.pallas_call without interpret= plumbing cannot fall back off "
         "TPU — thread kernels through kernels.backend.resolve_interpret",
         example="pl.pallas_call(kern, grid=(4,), out_shape=out)(x)",
         fix="pl.pallas_call(kern, grid=(4,), out_shape=out, "
             "interpret=resolve_interpret(interpret))(x)"),
    Rule("PLT004", "pallas-grid-mismatch", "error",
         "BlockSpec index_map arity must match the grid rank and return "
         "one coordinate per block dim",
         example="pl.pallas_call(kern, grid=(4, 4), in_specs=[pl.BlockSpec"
                 "((8, 128), lambda i: (i, 0))], ...)",
         fix="one lambda arg per grid axis, one returned coordinate per "
             "block dim: lambda i, j: (i, 0)"),
    Rule("PLT005", "backend-detect-dup", "error",
         "jax.default_backend() probed outside kernels/backend.py: use the "
         "canonical on_cpu/off_tpu/resolve_interpret helpers",
         example="def probe():\n    return jax.default_backend() != 'tpu'",
         fix="from repro.kernels.backend import off_tpu (the single "
             "cached probe site)"),
    Rule("PLT006", "paged-kv-page-size", "error",
         "KV page_size= must be positive and a multiple of 8: pages land in "
         "the kernel sublane dim, and an illegal page size silently forces "
         "interpret-only paged attention",
         example="SchedulerConfig(paged=True, page_size=12)",
         fix="pick a positive multiple of 8 (the repo default is 16)"),
    Rule("JXP001", "jaxpr-host-callback", "error",
         "a callback primitive (debug_callback/debug_print/pure_callback/"
         "io_callback) compiled into a registered serving stage: every "
         "dispatch pays a host round-trip the transfer guard cannot see",
         example="def step(x):\n    jax.debug.print(\"x={x}\", x=x)\n"
                 "    return x + 1\n# registered as a jitted serving stage",
         fix="strip debug prints from serving stages before registering "
             "them; log from the host side of the poll loop instead"),
    Rule("JXP002", "jaxpr-device-put", "error",
         "a device_put primitive compiled into a registered serving stage: "
         "a host value is being uploaded inside the traced graph instead "
         "of through the scheduler's explicit cached-upload paths",
         example="def step(x):\n    return x + jax.device_put(np.float32"
                 "(1.0))\n# registered as a jitted serving stage",
         fix="upload host scalars outside the stage (see _chunk_t0 / "
             "_thr_device) and pass them as arguments"),
    Rule("JXP003", "jaxpr-large-constant", "error",
         "a constant above the size threshold is folded into a registered "
         "stage's jaxpr — a closure-captured device array proven at the "
         "compiled level (the TRC006 hazard, no longer a syntactic guess)",
         example="table = jnp.zeros((512, 256))\nstage = jax.jit(lambda "
                 "i: table[i])\n# registered as a jitted serving stage",
         fix="pass the array as a stage argument so donation/aliasing "
             "work and replacing it cannot silently retrace"),
    Rule("JXP004", "jaxpr-cache-dtype-drift", "error",
         "a registered stage returns its cache with different leaf dtypes "
         "than it received — silent convert_element_type widening on the "
         "cache path breaks paged/contiguous and spec/target bit-parity",
         example="def step(cache, x):\n    return cache.astype(jnp."
                 "float32) + x   # bf16 cache comes back f32",
         fix="write cache updates back in the cache's own dtype "
             "(.astype(a.dtype) at the merge/scatter, as merge_decode_"
             "cache does)"),
    Rule("JXP005", "jaxpr-donation-violation", "error",
         "a stage declares donate_argnums but a donated buffer matches no "
         "output shape/dtype, so XLA cannot alias it in place — the "
         "donation silently degrades to a copy (and a warning at runtime)",
         example="stage = jax.jit(lambda c: c.sum(), donate_argnums=(0,))",
         fix="only donate dead-after-call buffers that come back as "
             "outputs (cache in -> cache out); drop the argnum otherwise"),
    Rule("CST001", "cost-graph-drift", "error",
         "compiled-stage FLOPs per token drifted outside the committed "
         "tolerance band around the analytic cost the admission router "
         "prices with — tier routing decisions are no longer grounded in "
         "what the stages actually compute",
         example="# core/cost_model._layer_flops drops the FFN term while\n"
                 "# the compiled decode stage still runs it",
         fix="re-derive core/cost_model._layer_flops for the changed "
             "architecture (or widen analysis/costcheck.TOLERANCE with a "
             "written justification in docs/invariants.md)"),
    Rule("SYN001", "poll-implicit-concretize", "error",
         ".item()/.tolist()/int()/float() directly on a jitted-stage "
         "output inside a poll hot method: a hidden per-call device sync "
         "that serializes the overlapped decode pipeline",
         example="class Pool:\n    def poll(self):\n        out = self."
                 "_decode(self.cache)\n        return out.item()",
         fix="defer the readback and batch it: tok = int(jax.device_get"
             "(out)) at the ONE intended sync point per readback window"),
    Rule("SYN002", "poll-host-numpy-sync", "error",
         "np.* called on a jitted-stage output inside a poll hot method "
         "without an explicit jax.device_get: the conversion is a hidden "
         "blocking transfer the transfer guard only catches at runtime",
         example="class Pool:\n    def poll(self):\n        out = self."
                 "_decode(self.cache)\n        return np.asarray(out)",
         fix="make the sync explicit and batched: np.asarray(jax."
             "device_get(out)) — one readback per window, visible in "
             "the source"),
    Rule("SYN003", "poll-block-until-ready", "error",
         ".block_until_ready() inside a poll hot method stalls the host "
         "on every dispatch, defeating double-buffered decode (the device "
         "queue should stay >=1 window deep)",
         example="class Pool:\n    def poll(self):\n        out = self."
                 "_decode(self.cache)\n        out.block_until_ready()",
         fix="drop the barrier from the hot loop; the batched jax."
             "device_get at the readback boundary already synchronizes "
             "(benchmarks may block OUTSIDE poll)"),
    Rule("PARSE", "unparseable-file", "error",
         "file failed to parse; the analyzer cannot vouch for it",
         example="def broken(:",
         fix="fix the syntax error; the analyzer skips nothing it cannot "
             "parse"),
]

RULES: Dict[str, Rule] = {r.id: r for r in _ALL}
