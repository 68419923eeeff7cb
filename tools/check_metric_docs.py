"""Metric/doc drift gate: every registered family must be in the docs.

Constructs the serving stack's default registries (model server with
every conditional family enabled, gateway, cache service, kv-pool,
moderation), walks every family name registered in
``obs/registry.py``'s process-wide census, and fails when one is
missing from the ``docs/observability.md`` catalog. PR 3 hand-audited
that catalog once; this tool makes the audit a tier-1 test
(``tests/test_metric_docs.py``) so a new family without its doc row —
or a doc row whose name drifted from the code — can't land again.

Doc-side matching understands the catalog's notation: backtick code
spans, ``{a,b,c}`` brace alternation
(``llm_cache_{exact_hits,misses}_total``), trailing label selectors
(``llm_handoff_total{event=…}``), and ``*`` globs
(``llm_prefix_cache_*``).

The same census also lints the shipped Grafana dashboard
(``deploy/k8s/monitoring/grafana-dashboard.json``): every metric
family a panel expression references must exist in a default registry
AND in the docs catalog — a renamed family otherwise leaves the
dashboard silently flat (``[grafana]`` findings). Histogram
``_bucket``/``_sum``/``_count`` sample suffixes resolve to their base
family first.

Third pass, the **HBM ledger owner census** (``[hbm-ledger]``
findings): every account name booked anywhere in the stack — a string
literal passed to ``book``/``pulse``/``note_reclaim``/``transfer``
(f-string fields normalize to ``*``, so ``f"adapters/r{rb}"`` checks
as ``adapters/r*``) — must match a pattern in the
``docs/observability.md`` "Memory plane" account glossary. An account
booked at a call site but absent from the glossary is exactly the
drift the ledger exists to prevent: bytes with an owner nobody can
look up.

Run standalone: ``python tools/check_metric_docs.py``. Report lines and
exit codes follow the repo's shared checker contract
(``tools/graftlint/report.py``): rc 0 clean, rc 1 on drift, rc 2 on an
internal error — same shape ``python -m tools.graftlint`` emits, so
tier-1 logs and CI greps read identically across checkers.
"""

from __future__ import annotations

import fnmatch
import itertools
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DOC = os.path.join(REPO, "docs", "observability.md")
GRAFANA = os.path.join(REPO, "deploy", "k8s", "monitoring",
                       "grafana-dashboard.json")

_CODE_SPAN = re.compile(r"`([^`]+)`")
_NAME_TOKEN = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:{},*]*")
# our families all carry one of the stack's prefixes; PromQL function
# names / label names never match, so a bare word-boundary scan of the
# expression string is enough
_EXPR_METRIC = re.compile(
    r"\b((?:llm|gateway|kvpool|moderation)_[a-zA-Z0-9_]+)")
_HISTO_SUFFIXES = ("_bucket", "_count", "_sum")

# a ledger booking call with a literal owner: any callable ending in
# book/pulse/note_reclaim/transfer (methods AND wrappers like the
# engine's _hbm_book) whose first argument is a (possibly f-) string
_LEDGER_CALL = re.compile(
    r"(?:book|pulse|note_reclaim|transfer)\(\s*(f?)([\"'])([^\"']+)\2")
# directories whose booking call sites the owner census walks
_LEDGER_SRC_DIRS = ("llm_in_practise_tpu", "tools")
# the docs glossary table row: | `account` | plane | booked by |
_GLOSSARY_ROW = re.compile(r"^\|\s*`([^`\s]+)`\s*\|")


def doc_patterns(md_text: str) -> set[str]:
    """Metric-name patterns declared by the doc's code spans (and the
    bodies of fenced ```promql blocks — a family referenced only from
    an example query still counts as documented)."""
    spans: list[str] = []
    in_fence = False
    for line in md_text.split("\n"):
        if line.lstrip().startswith("```"):
            # fences toggle; pairing ` across a fence line would skew
            # every span after it (the bug a whole-file regex has)
            in_fence = not in_fence
            continue
        if in_fence:
            spans.append(line)
        else:
            spans.extend(_CODE_SPAN.findall(line))
    out: set[str] = set()
    for span in spans:
        for token in _NAME_TOKEN.findall(span):
            # drop a trailing label selector: name{event=…} -> name
            # (the token regex stops at '=' so the brace never closes;
            # brace ALTERNATION closes inside the token and expands)
            if "{" in token:
                head, brace = token.split("{", 1)
                if "}" not in brace or "=" in brace:
                    token = head
            if not token:
                continue
            out.update(_expand_braces(token))
    return out


def _expand_braces(token: str) -> list[str]:
    """``a_{x,y}_b`` -> [``a_x_b``, ``a_y_b``] (multiple groups too)."""
    parts: list[list[str]] = []
    rest = token
    while "{" in rest:
        head, rest = rest.split("{", 1)
        if "}" not in rest:      # malformed span: treat literally
            return [token.replace("{", "").replace("}", "")]
        group, rest = rest.split("}", 1)
        parts.append([head])
        parts.append(group.split(","))
    parts.append([rest])
    return ["".join(combo) for combo in itertools.product(*parts)]


def collect_registered() -> frozenset[str]:
    """Construct the stack's default registries (conditional families
    forced ON) and return the union of their family names."""
    import jax
    import jax.numpy as jnp

    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from llm_in_practise_tpu.serve.cache_service import CacheService
    from llm_in_practise_tpu.serve.engine import InferenceEngine
    from llm_in_practise_tpu.serve.gateway import (
        Gateway, ResponseCache, Router, Upstream,
    )
    from llm_in_practise_tpu.serve.kv_pool import KVPoolServer
    from llm_in_practise_tpu.serve.moderation import ModerationService

    class _Tok:
        def encode(self, text):
            return list(text.encode()[:32])

        def decode(self, ids):
            return bytes(int(i) % 256 for i in ids).decode(
                "utf-8", "replace")

    cfg = GPTConfig(vocab_size=256, seq_len=64, n_layer=1, n_head=2,
                    embed_dim=16, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    # every conditional family ON: prefix cache, speculation, paged
    # KV — their metric families must be documented too
    engine = InferenceEngine(model, params, max_slots=2, cache_len=64,
                             cache_dtype=jnp.float32, prefix_cache=True,
                             speculative_k=2, kv_layout="paged")
    owners = [
        OpenAIServer(engine, _Tok(), model_name="census"),
        Gateway(Router([Upstream("http://127.0.0.1:1", "census",
                                 group="census")]),
                cache=ResponseCache(semantic_threshold=None),
                health_check_interval_s=0),
        CacheService(),
        ModerationService(),
        KVPoolServer(),     # registry built in __init__; never started
    ]
    engine.stop()
    names: set[str] = set()
    for owner in owners:
        reg = getattr(owner, "registry", None)
        if reg is None:          # moderation builds its registry lazily
            owner.metrics_text()
            reg = owner._registry
        names |= reg.family_names()
    return frozenset(names)


def check(registered=None, md_text: str | None = None) -> list[str]:
    """Families registered but absent from the doc catalog (sorted)."""
    if registered is None:
        registered = collect_registered()
    if md_text is None:
        with open(DOC, encoding="utf-8") as f:
            md_text = f.read()
    patterns = doc_patterns(md_text)
    missing = []
    for name in sorted(registered):
        if name in patterns:
            continue
        if any("*" in p and fnmatch.fnmatch(name, p) for p in patterns):
            continue
        missing.append(name)
    return missing


def grafana_metric_refs(dash: dict) -> list[tuple[str, str]]:
    """``(panel title, family name)`` pairs for every metric family a
    dashboard panel expression references (deduplicated, ordered)."""
    out: list[tuple[str, str]] = []
    seen: set[tuple[str, str]] = set()
    for panel in dash.get("panels", []):
        title = str(panel.get("title", f"panel {panel.get('id')}"))
        for target in panel.get("targets", []):
            for m in _EXPR_METRIC.finditer(str(target.get("expr", ""))):
                pair = (title, m.group(1))
                if pair not in seen:
                    seen.add(pair)
                    out.append(pair)
    return out


def check_grafana(registered=None, md_text: str | None = None,
                  dash: dict | None = None) -> list[str]:
    """Dashboard families that are unregistered or undocumented."""
    if registered is None:
        registered = collect_registered()
    if md_text is None:
        with open(DOC, encoding="utf-8") as f:
            md_text = f.read()
    if dash is None:
        with open(GRAFANA, encoding="utf-8") as f:
            dash = json.load(f)
    patterns = doc_patterns(md_text)

    def documented(name: str) -> bool:
        return (name in patterns
                or any("*" in p and fnmatch.fnmatch(name, p)
                       for p in patterns))

    findings = []
    for title, name in grafana_metric_refs(dash):
        # histogram panels reference rendered samples
        # (…_seconds_bucket); registration and the catalog both speak
        # in the base family
        base = name
        for suffix in _HISTO_SUFFIXES:
            if name.endswith(suffix) and name[: -len(suffix)] in registered:
                base = name[: -len(suffix)]
                break
        problems = []
        if base not in registered:
            problems.append("not registered by any default registry")
        if not (documented(base) or documented(name)):
            problems.append("missing from the docs catalog")
        if problems:
            findings.append(
                f"panel {title!r} references {name}: "
                + " AND ".join(problems))
    return findings


def ledger_accounts(root: str = REPO) -> dict[str, list[str]]:
    """``account pattern -> ["path:line", ...]`` for every literal
    owner booked anywhere in the stack. f-string replacement fields
    normalize to ``*`` so dynamic owners (``f"adapters/r{rb}"``) still
    census as one pattern."""
    out: dict[str, list[str]] = {}
    for top in _LEDGER_SRC_DIRS:
        for dirpath, _dirnames, filenames in os.walk(
                os.path.join(root, top)):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as f:
                    for lineno, line in enumerate(f, 1):
                        for m in _LEDGER_CALL.finditer(line):
                            owner = m.group(3)
                            if m.group(1):      # f-string: {rb} -> *
                                owner = re.sub(r"\{[^{}]*\}", "*", owner)
                            site = (f"{os.path.relpath(path, root)}"
                                    f":{lineno}")
                            out.setdefault(owner, []).append(site)
    return out


def glossary_patterns(md_text: str | None = None) -> set[str]:
    """Account patterns from the docs "Memory plane" glossary table
    (first cell of each row), ``*`` globs included."""
    if md_text is None:
        with open(DOC, encoding="utf-8") as f:
            md_text = f.read()
    out: set[str] = set()
    in_section = False
    for line in md_text.split("\n"):
        if line.startswith("### "):
            in_section = line.startswith("### Memory plane")
            continue
        if in_section:
            m = _GLOSSARY_ROW.match(line)
            if m and m.group(1) not in ("account",):
                out.add(m.group(1))
    return out


def check_ledger_owners(md_text: str | None = None,
                        accounts: dict | None = None) -> list[str]:
    """Booked accounts missing from the docs glossary (sorted; one
    finding per account, anchored at its first call site)."""
    patterns = glossary_patterns(md_text)
    if accounts is None:
        accounts = ledger_accounts()
    findings = []
    for owner in sorted(accounts):
        if owner in patterns:
            continue
        if any("*" in p and fnmatch.fnmatch(owner, p) for p in patterns):
            continue
        findings.append(
            f"{accounts[owner][0]}: [hbm-ledger] account {owner!r} is "
            "booked here but missing from the docs/observability.md "
            "Memory-plane glossary")
    return findings


def main() -> int:
    from tools.graftlint import report

    doc_rel = os.path.relpath(DOC, REPO)
    dash_rel = os.path.relpath(GRAFANA, REPO)
    try:
        registered = collect_registered()
        missing = check(registered=registered)
        grafana = check_grafana(registered=registered)
        ledger = check_ledger_owners()
    except Exception as e:  # noqa: BLE001 — a broken registry census is
        # an internal error (rc 2), not "zero drift"
        print(f"check_metric_docs: cannot build the registry census: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return report.EXIT_ERROR
    return report.emit(
        "check_metric_docs",
        [f"{doc_rel}: [metric-docs] {name}: registered metric family "
         "missing from the docs catalog" for name in missing]
        + [f"{dash_rel}: [grafana] {line}" for line in grafana]
        + ledger,
        ok_summary=(f"every registered metric family is documented in "
                    f"{doc_rel}; every {dash_rel} panel expression "
                    "resolves to a registered, documented family; every "
                    "booked HBM-ledger account is in the Memory-plane "
                    "glossary"),
        fail_hint="Add a catalog row / glossary row "
                  "(docs/observability.md) for each, or fix the "
                  "drifted name.")


if __name__ == "__main__":
    sys.exit(main())
