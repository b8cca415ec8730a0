"""Rule family 3 — registry cross-checks.

The fault-injection points, the metric surface, and the health rules
are each declared twice: once in code, once in a registry the humans
read (RESILIENCE.md tables, METRICS.md table, `health.DEFAULT_RULES`).
These rules diff the two views so they cannot drift:

  registry.fault-site-undocumented  a `fire("x.y")` call site whose
                                    point is missing from RESILIENCE.md
  registry.fault-site-unwired       a RESILIENCE.md table row no code
                                    fires
  registry.invariant-undocumented   a `chaos.*` invariant name checked
                                    by an auditor but missing from the
                                    RESILIENCE.md invariant table
  registry.invariant-unchecked      a RESILIENCE.md invariant-table row
                                    no auditor checks
  registry.metric-undocumented      a metric key referenced in code
                                    (emitted OR read) missing from
                                    METRICS.md
  registry.metric-unemitted         a METRICS.md row nothing in code
                                    references
  registry.health-rule-metric       a HealthRule.metric naming a row no
                                    code emits
  registry.prometheus               a code metric key that renders into
                                    an invalid Prometheus exposition
                                    line (shared validate_prometheus_text)

Metric-key extraction is deliberately syntactic: any string literal of
shape ``family/name`` in the trainer/orchestrator/telemetry/sampler
modules counts, plus f-strings whose constant segments look like metric
keys (``f"fleet/{k}"``, ``f"health/rule_{name}"``, ``f"{p}/staleness_
hist_{k}"``) which are matched as patterns. Doc-side wildcards
(``health/rule_<name>``, trailing ``_K``, ``{reason="..."}`` labels,
``{a,b}`` brace lists) are expanded/normalized symmetrically. Bare keys
without a slash (``lr``, ``episode``) are out of scope — indistinguishable
from ordinary strings.

Histogram families (keys under ``hist.HISTOGRAM_KEY_PREFIX``) get shape-
aware treatment: the Prometheus surface derives three sample names per
family (``_bucket{le="..."}``/``_sum``/``_count``), so both cross-check
directions fold such suffixes back to the family before diffing, and the
prometheus rule renders these keys through the real histogram exposition
path instead of the gauge renderer.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from .engine import Finding, Project, dotted_name

METRIC_SCOPES = (
    "nanorlhf_tpu/trainer/",
    "nanorlhf_tpu/orchestrator/",
    "nanorlhf_tpu/telemetry/",
    "nanorlhf_tpu/sampler/",
    "nanorlhf_tpu/serving/",             # gateway/engine emit serving/*
    "nanorlhf_tpu/loadgen/",             # traffic harness emits loadgen/*
    "nanorlhf_tpu/envs/",                # episode driver emits env/*
    "nanorlhf_tpu/utils/profiling.py",   # PhaseTimer emits time/{k}_s
    "nanorlhf_tpu/ops/moe.py",           # moe_counters names the trainer's moe/*
)

# slash-shaped literals that are not metric keys (HTTP content types)
_NOT_METRICS = {"text/plain", "text/html", "application/json",
                "application/octet-stream"}

_KEY_RE = re.compile(r'^[a-z][a-z0-9_]*/[a-z0-9_]+(\{[a-z_]+="[^"]*"\})?$')
_FSTR_SEG_RE = re.compile(r'^[a-z0-9_/{}="]*$')
_FAULT_RE = re.compile(r"^[a-z_]+\.[a-z_]+$")
# chaos run-invariant names (chaos/auditors.py INVARIANTS) — collected
# only from nanorlhf_tpu/chaos/ modules, diffed against the RESILIENCE.md
# `| invariant |` table in both directions
_INVARIANT_RE = re.compile(r"^chaos\.[a-z_]+$")
_INVARIANT_SCOPE = "nanorlhf_tpu/chaos/"

# histogram metric families (telemetry/hist.py): a key under this prefix
# is exported as Prometheus HISTOGRAM exposition — three derived sample
# names per family (`<f>_bucket{le="..."}`, `<f>_sum`, `<f>_count`)
# instead of one gauge line. METRICS.md documents the FAMILY name once;
# the cross-check below normalizes both directions (a doc row carrying an
# explicit suffix/label, or a code literal building one, folds back to
# its family before the diff).
try:
    from nanorlhf_tpu.telemetry.hist import HISTOGRAM_KEY_PREFIX
except Exception:  # pragma: no cover - hist.py is jax-free
    HISTOGRAM_KEY_PREFIX = "latency/"

_HIST_SUFFIX_RE = re.compile(r'(_bucket(\{le="[^"]*"\})?|_sum|_count)$')


def hist_family(name: str) -> str:
    """Fold a histogram sample name back to its family key: strip one
    `_bucket{le="..."}`/`_bucket`/`_sum`/`_count` suffix from keys under
    the histogram prefix; every other name passes through unchanged."""
    if not name.startswith(HISTOGRAM_KEY_PREFIX):
        return name
    return _HIST_SUFFIX_RE.sub("", name)


# ---------------------------------------------------------------------------
# doc parsing
# ---------------------------------------------------------------------------

def parse_fault_tables(text: str) -> set[str]:
    """Backticked first-cell names from RESILIENCE.md `| point |` tables."""
    sites: set[str] = set()
    in_table = False
    for line in text.splitlines():
        s = line.strip()
        if s.startswith("|") and "point" in s.split("|")[1]:
            in_table = True
            continue
        if not s.startswith("|"):
            in_table = False
            continue
        if in_table:
            first = s.split("|")[1]
            for tok in re.findall(r"`([^`]+)`", first):
                if _FAULT_RE.match(tok):
                    sites.add(tok)
    return sites


def parse_invariant_tables(text: str) -> set[str]:
    """Backticked first-cell names from RESILIENCE.md `| invariant |`
    tables — same grammar as the fault-site tables, different header."""
    names: set[str] = set()
    in_table = False
    for line in text.splitlines():
        s = line.strip()
        if s.startswith("|") and "invariant" in s.split("|")[1].lower():
            in_table = True
            continue
        if not s.startswith("|"):
            in_table = False
            continue
        if in_table:
            first = s.split("|")[1]
            for tok in re.findall(r"`([^`]+)`", first):
                if _INVARIANT_RE.match(tok):
                    names.add(tok)
    return names


def parse_metric_doc(text: str) -> tuple[set[str], list[str]]:
    """(exact names, wildcard names-with-'*') from METRICS.md first cells."""
    exact: set[str] = set()
    wild: list[str] = []
    for line in text.splitlines():
        s = line.strip()
        if not s.startswith("|") or s.startswith("|---") or "Metric" in s[:10]:
            continue
        first = s.split("|")[1]
        for tok in re.findall(r"`([^`]+)`", first):
            for name in _expand_doc_name(tok):
                if "*" in name:
                    wild.append(name)
                elif "/" in name:
                    exact.add(name)
                # bare names (lr, episode) are out of scope
    return exact, wild


def _expand_doc_name(tok: str) -> list[str]:
    # brace list: time/{rollout,reward}_s -> time/rollout_s, time/reward_s
    m = re.match(r"^([^{]*)\{([a-z0-9_,]+)\}(.*)$", tok)
    if m and "," in m.group(2):
        return [x for part in m.group(2).split(",")
                for x in _expand_doc_name(m.group(1) + part + m.group(3))]
    name = tok
    name = re.sub(r"<[^>]+>", "*", name)           # health/rule_<name>
    name = name.replace('"..."', '"*"')            # {reason="..."} label
    if re.search(r"_K$", name):                    # staleness_hist_K
        name = name[:-1] + "*"
    return [name]


# ---------------------------------------------------------------------------
# code extraction
# ---------------------------------------------------------------------------

class _CodeInventory(ast.NodeVisitor):
    def __init__(self, relpath: str, collect_metrics: bool,
                 collect_invariants: bool = False):
        self.relpath = relpath
        self.collect_metrics = collect_metrics
        self.collect_invariants = collect_invariants
        self.fires: list[tuple[str, int]] = []          # (point, line)
        self.keys: list[tuple[str, int]] = []           # (literal key, line)
        self.patterns: list[tuple[str, int]] = []       # (regex source, line)
        self.health_metrics: list[tuple[str, int]] = []
        self.invariants: list[tuple[str, int]] = []     # (chaos.* name, line)
        self._not_keys: set[int] = set()   # Constant node ids to skip

    def visit_Call(self, node: ast.Call):
        name = dotted_name(node.func)
        if isinstance(node.func, ast.Attribute) and node.func.attr == "fire" \
                and node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            self.fires.append((node.args[0].value, node.lineno))
        if name and name.split(".")[-1] == "HealthRule":
            for kw in node.keywords:
                if kw.arg == "metric" and isinstance(kw.value, ast.Constant):
                    self.health_metrics.append((kw.value.value, node.lineno))
                    # a HealthRule WATCHING a row is not an emission of it —
                    # counting it as a key would make health-rule-metric
                    # vacuously satisfied by its own argument
                    self._not_keys.add(id(kw.value))
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant):
        if self.collect_metrics and isinstance(node.value, str) \
                and node.value not in _NOT_METRICS \
                and id(node) not in self._not_keys \
                and _KEY_RE.match(node.value):
            self.keys.append((node.value, node.lineno))
        if self.collect_invariants and isinstance(node.value, str) \
                and _INVARIANT_RE.match(node.value):
            self.invariants.append((node.value, node.lineno))

    def visit_JoinedStr(self, node: ast.JoinedStr):
        if not self.collect_metrics:
            return
        segs: list[str] = []
        ok = True
        has_slash = False
        for part in node.values:
            if isinstance(part, ast.Constant) and isinstance(part.value, str):
                if not _FSTR_SEG_RE.match(part.value):
                    ok = False
                    break
                has_slash = has_slash or "/" in part.value
                segs.append(re.escape(part.value))
            else:
                segs.append(".*")
        if ok and has_slash and any(s != ".*" for s in segs):
            self.patterns.append(("^" + "".join(segs) + "$", node.lineno))
        # do not recurse: inner constants of an f-string aren't standalone keys


def run(proj: Project) -> list[Finding]:
    findings: list[Finding] = []
    root = proj.root

    res_md = root / "docs" / "RESILIENCE.md"
    met_md = root / "docs" / "METRICS.md"
    res_text = res_md.read_text() if res_md.exists() else ""
    doc_sites = parse_fault_tables(res_text)
    doc_invariants = parse_invariant_tables(res_text)
    doc_exact, doc_wild = (parse_metric_doc(met_md.read_text())
                           if met_md.exists() else (set(), []))

    fires: dict[str, tuple[str, int]] = {}
    keys: dict[str, tuple[str, int]] = {}
    patterns: list[tuple[str, str, int]] = []   # (regex, path, line)
    health: list[tuple[str, str, int]] = []
    invariants: dict[str, tuple[str, int]] = {}
    for src in proj.iter_trees():
        in_scope = src.relpath.startswith(METRIC_SCOPES)
        inv = _CodeInventory(src.relpath, in_scope,
                             src.relpath.startswith(_INVARIANT_SCOPE))
        inv.visit(src.tree)
        for point, line in inv.fires:
            fires.setdefault(point, (src.relpath, line))
        for k, line in inv.keys:
            keys.setdefault(k, (src.relpath, line))
        patterns.extend((rx, src.relpath, line) for rx, line in inv.patterns)
        health.extend((m, src.relpath, line) for m, line in inv.health_metrics)
        for name, line in inv.invariants:
            invariants.setdefault(name, (src.relpath, line))

    # --- fault sites <-> RESILIENCE.md -------------------------------------
    for point, (path, line) in sorted(fires.items()):
        if point not in doc_sites:
            findings.append(Finding(
                rule="registry.fault-site-undocumented", path=path, line=line,
                detail=f"fire:{point}",
                message=f'fire("{point}") has no row in the RESILIENCE.md '
                        f"fault-site tables"))
    for point in sorted(doc_sites - set(fires)):
        findings.append(Finding(
            rule="registry.fault-site-unwired", path="docs/RESILIENCE.md",
            line=1, detail=f"doc:{point}",
            message=f"RESILIENCE.md documents fault point `{point}` but no "
                    f'code calls fire("{point}")'))

    # --- chaos invariants <-> RESILIENCE.md --------------------------------
    for name, (path, line) in sorted(invariants.items()):
        if name not in doc_invariants:
            findings.append(Finding(
                rule="registry.invariant-undocumented", path=path, line=line,
                detail=f"invariant:{name}",
                message=f"chaos invariant '{name}' has no row in the "
                        f"RESILIENCE.md invariant table"))
    for name in sorted(doc_invariants - set(invariants)):
        findings.append(Finding(
            rule="registry.invariant-unchecked", path="docs/RESILIENCE.md",
            line=1, detail=f"doc:{name}",
            message=f"RESILIENCE.md documents invariant `{name}` but no "
                    f"chaos auditor checks it"))

    # --- metric keys <-> METRICS.md ----------------------------------------
    wild_prefixes = [w.split("*")[0] for w in doc_wild]

    def documented(key: str) -> bool:
        if key in doc_exact or any(
                key.startswith(p) and p for p in wild_prefixes):
            return True
        # histogram shape: a code literal naming an exposition sample
        # (`latency/x_s_count`) is covered by its documented family row
        fam = hist_family(key)
        return fam != key and documented(fam)

    for key, (path, line) in sorted(keys.items()):
        if not documented(key):
            findings.append(Finding(
                rule="registry.metric-undocumented", path=path, line=line,
                detail=f"key:{key}",
                message=f"metric key '{key}' referenced in code but absent "
                        f"from docs/METRICS.md (add a row, or fix the key "
                        f"if it is a typo for an existing row)"))

    pattern_res = [(re.compile(rx), path, line) for rx, path, line in patterns]
    for rx, path, line in pattern_res:
        probe_ok = any(rx.match(d) for d in doc_exact) or any(
            rx.match(w.replace("*", "x")) for w in doc_wild)
        if not probe_ok:
            findings.append(Finding(
                rule="registry.metric-undocumented", path=path, line=line,
                detail=f"pattern:{rx.pattern}",
                message=f"metric f-string pattern {rx.pattern} matches no "
                        f"documented METRICS.md row"))

    def emitted(doc_name: str) -> bool:
        probe = doc_name.replace("*", "x")
        if doc_name.rstrip("*") and "*" in doc_name:
            # wildcard doc rows: emitted if a code pattern or literal shares
            # the prefix
            pre = doc_name.split("*")[0]
            if any(k.startswith(pre) for k in keys):
                return True
            if any(rx.match(probe) for rx, _, _ in pattern_res):
                return True
        elif doc_name in keys or any(rx.match(doc_name)
                                     for rx, _, _ in pattern_res):
            return True
        # histogram shape, doc→code direction: a doc row spelling an
        # exposition suffix (`latency/x_s_bucket{le="..."}` — the `...`
        # label arrives here as `*`) is emitted when code references the
        # family it derives from
        fam = hist_family(probe)
        return fam != probe and emitted(fam)

    for doc_name in sorted(doc_exact) + sorted(doc_wild):
        if not emitted(doc_name):
            findings.append(Finding(
                rule="registry.metric-unemitted", path="docs/METRICS.md",
                line=1, detail=f"doc:{doc_name}",
                message=f"METRICS.md documents '{doc_name}' but no scoped "
                        f"module references it"))

    # --- HealthRule.metric must be an emitted row --------------------------
    for metric, path, line in health:
        if not (metric in keys or documented(metric)
                or any(rx.match(metric) for rx, _, _ in pattern_res)):
            findings.append(Finding(
                rule="registry.health-rule-metric", path=path, line=line,
                detail=f"health:{metric}",
                message=f"HealthRule watches metric '{metric}' but nothing "
                        f"emits that row — the rule can never fire"))

    # --- Prometheus name validity via the shared validator -----------------
    findings.extend(_prometheus_check(keys))
    return findings


def _prometheus_check(keys: dict[str, tuple[str, int]]) -> list[Finding]:
    try:
        from nanorlhf_tpu.telemetry.exporter import (
            render_prometheus, render_prometheus_histograms,
            validate_prometheus_text)
        from nanorlhf_tpu.telemetry.hist import StreamingHistogram
    except Exception as e:  # pragma: no cover - exporter is jax-free
        return [Finding(
            rule="registry.prometheus", path="nanorlhf_tpu/telemetry/exporter.py",
            line=1, detail="import",
            message=f"could not import the shared Prometheus validator: {e}")]
    probe_hist = StreamingHistogram()
    probe_hist.record(0.05)
    out: list[Finding] = []
    for key, (path, line) in sorted(keys.items()):
        if key.startswith(HISTOGRAM_KEY_PREFIX):
            # histogram families render through the histogram exposition
            # path — the derived _bucket/_sum/_count sample names and the
            # le label are what must survive the validator
            text = render_prometheus_histograms(
                {hist_family(key): probe_hist.state()})
        else:
            text = render_prometheus({key: 1.0})
        errors = validate_prometheus_text(text)
        for err in errors:
            out.append(Finding(
                rule="registry.prometheus", path=path, line=line,
                detail=f"prom:{key}",
                message=f"metric key '{key}' renders to invalid Prometheus "
                        f"exposition text: {err}"))
    return out
