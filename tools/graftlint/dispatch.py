"""Pass 1 — dispatch hygiene.

Where host dispatch rivals the device step (docs/perf.md Finding 5) a
stray host-device sync in the engine's hot loop IS the
latency model: one ``np.asarray`` on an in-flight array stalls every
slot's decode block (the TPOT collapses Findings 13/14/17 chased).

Rules:

- ``host-sync`` — host-forcing constructs (``jax.block_until_ready``,
  ``jax.device_get``, ``.item()``, ``np.asarray``/``np.array``, and
  ``float()``/``bool()``/``int()`` directly over a jitted call's result)
  inside functions statically reachable from the engine step. The
  engine's *deliberate* force-points — the places that stamp an honest
  ``dt`` for :meth:`DispatchMeter.note_phase` before booking a
  device-plane sample — are allowlisted in ``baseline.toml``.
- ``tracer-bool`` — ``if``/``while`` over a traced parameter inside a
  jit-wrapped function body: under trace this either raises a
  ConcretizationError at runtime or (with static shapes) silently bakes
  one branch per compilation — a per-value recompile hazard.
"""

from __future__ import annotations

import ast

from tools.graftlint.callgraph import CallGraph
from tools.graftlint.core import Finding, SourceFile, call_name, dotted
from tools.graftlint.jitindex import JitIndex

#: the engine hot loop's entry points (qualnames)
ENGINE_ROOTS = (
    "InferenceEngine.step",
    "InferenceEngine._step_locked",
)

_FORCING_CALLS = {
    "jax.block_until_ready": "forces every leaf to finish on device",
    "jax.device_get": "synchronous device->host copy",
    "np.asarray": "materializes (and blocks on) a device array",
    "np.array": "materializes (and blocks on) a device array",
    "numpy.asarray": "materializes (and blocks on) a device array",
    "numpy.array": "materializes (and blocks on) a device array",
}

_FORCING_METHODS = {
    "item": "scalar device->host sync",
    "block_until_ready": "forces the array to finish on device",
}


def _jitted_call_names(jit_index: JitIndex) -> set[str]:
    out = set()
    for site in jit_index.sites:
        if site.bound_attr:
            out.add(site.bound_attr)
    return out


def run(files: list[SourceFile], graph: CallGraph,
        jit_index: JitIndex) -> list[Finding]:
    findings: list[Finding] = []
    reachable = graph.reachable_from(list(ENGINE_ROOTS))
    jitted_names = _jitted_call_names(jit_index)

    for info in sorted(reachable, key=lambda i: (i.sf.rel,
                                                 i.node.lineno)):
        sf = info.sf
        for node in ast.walk(info.node):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            name = call_name(node)
            msg = None
            if d in _FORCING_CALLS:
                msg = f"{d}(...) — {_FORCING_CALLS[d]}"
            elif (isinstance(node.func, ast.Attribute)
                  and name in _FORCING_METHODS
                  and not isinstance(node.func.value, ast.Constant)):
                msg = f".{name}() — {_FORCING_METHODS[name]}"
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in ("float", "bool", "int")
                  and node.args):
                # only flag the unambiguous case: the argument IS a
                # jitted call's (device) result — float(self._decode(...))
                arg = node.args[0]
                if (isinstance(arg, ast.Call)
                        and call_name(arg) in jitted_names):
                    msg = (f"{node.func.id}() over a jitted call's "
                           "result — implicit device sync")
            if msg is None:
                continue
            finding = Finding(
                sf.rel, node.lineno, "host-sync", info.qualname,
                f"host-device sync on the engine step path: {msg} "
                "(allowlist deliberate force-points in baseline.toml)")
            if not sf.suppressed("host-sync", node):
                findings.append(finding)

    # tracer-bool: if/while over traced params inside jitted bodies
    for sf, fn, site in jit_index.jitted_defs:
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args)}
        params.discard("self")
        static = set(site.static_argnames)
        for i in site.static_argnums:
            ordered = [a.arg for a in fn.args.posonlyargs + fn.args.args
                       if a.arg != "self"]
            if 0 <= i < len(ordered):
                static.add(ordered[i])
        # keyword-only args are static-by-name only
        traced = params - static
        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            hit = None
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.Name) and sub.id in traced:
                    hit = sub.id
                    break
            if hit is None:
                continue
            if sf.suppressed("tracer-bool", node):
                continue
            findings.append(Finding(
                sf.rel, node.lineno, "tracer-bool", sf.qualname(fn),
                f"branch on traced parameter {hit!r} inside a jitted "
                "function — concretization error or per-value recompile; "
                "use lax.cond/where or declare it static"))
    return findings
