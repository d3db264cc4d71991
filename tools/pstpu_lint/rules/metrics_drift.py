"""PL004 metrics-drift: renderers, registry, and docs must agree.

The engine's /metrics is rendered by server/metrics.py (the histogram names
it renders live in engine/metrics.py) and the router's by its own
prometheus_client registry. A series a renderer emits that the registry
lacks, a registered series no renderer emits, a label set that differs from
the registry's, a name outside the ``pstpu:``/``router_``/``vllm:``
convention, a duplicate declaration, or a series missing from the docs
tables is exactly the silent drift the dashboards then chart wrong — or
chart nothing.

Checks, all against tools/pstpu_lint/metrics_registry.py:
  1. every statically-extracted series name uses an allowed prefix;
  2. no series is declared twice on one surface;
  3. each surface's extracted name set == the registry's set for it;
  4. extracted label sets match the registry;
  5. the generated docs tables (gen_docs markers) are up to date.
"""

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from tools.pstpu_lint import metrics_registry as reg
from tools.pstpu_lint.core import Finding

SERVER_METRICS = "production_stack_tpu/server/metrics.py"
ENGINE_METRICS = "production_stack_tpu/engine/metrics.py"
ROUTER_METRICS = "production_stack_tpu/router/metrics.py"

# name -> (kind, labels-or-None, line, relpath-or-None); labels None = not
# statically visible; relpath None = the surface's default renderer file
# (histogram names live in engine/metrics.py but render on the text surface,
# so their findings must point there).
Extracted = Dict[
    str, Tuple[str, Optional[Tuple[str, ...]], int, Optional[str]]
]


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _const_str_list(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, (ast.List, ast.Tuple)):
        vals = [_const_str(e) for e in node.elts]
        if all(v is not None for v in vals):
            return tuple(vals)
    return None


def _labels_from_source(name: str, source: str) -> Optional[Tuple[str, ...]]:
    """Label keys of a text-renderer emission, from the f-string source.

    ``name{label}`` uses the shared per-model label placeholder;
    ``name{{k="...",`` spells labels inline (possibly across a line break).
    Returns None when the emission is not statically visible (e.g. rendered
    through the histogram helper).
    """
    idx = source.find(name + "{")
    if idx < 0:
        return None
    window = source[idx + len(name):idx + len(name) + 220]
    if window.startswith("{label}"):
        return ("model_name",)
    if window.startswith("{{"):
        # Collect k=" keys up to the closing }} (f-string literals may be
        # split across adjacent string parts; the window spans them).
        end = window.find("}}")
        body = window[2:end if end > 0 else len(window)]
        keys = re.findall(r'(\w+)="', body)
        return tuple(dict.fromkeys(keys)) or None
    return None


def extract_engine_text(server_src: str,
                        engine_src: Optional[str] = None) -> Extracted:
    """Series of the text renderer: '# TYPE <name> <kind>' constants, plus
    the histogram names it renders via RequestLatencyHistograms."""
    out: Extracted = {}
    dupes: List[Tuple[str, int]] = []
    tree = ast.parse(server_src)
    for node in ast.walk(tree):
        val = _const_str(node)
        if val is None or not val.startswith("# TYPE "):
            continue
        parts = val.split()
        if len(parts) != 4:
            continue
        _h, _t, name, kind = parts
        line = node.lineno
        if name in out:
            dupes.append((name, line))
            continue
        out[name] = (kind, _labels_from_source(name, server_src), line, None)
    if engine_src:
        etree = ast.parse(engine_src)
        for node in ast.walk(etree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "render" and node.args):
                name = _const_str(node.args[0])
                if name and name.startswith(reg.ALLOWED_PREFIXES):
                    out.setdefault(
                        name,
                        ("histogram", None, node.lineno, ENGINE_METRICS),
                    )
    out["__duplicates__"] = dupes  # type: ignore[assignment]
    return out


def extract_router(router_src: str) -> Extracted:
    """Series of the router's prometheus_client module registry."""
    out: Extracted = {}
    dupes: List[Tuple[str, int]] = []
    tree = ast.parse(router_src)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        ctor = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if ctor not in ("Gauge", "Counter", "Histogram"):
            continue
        name = _const_str(node.args[0]) if node.args else None
        if name is None:
            continue
        kind = ctor.lower()
        if kind == "counter" and not name.endswith("_total"):
            name += "_total"
        labels: Optional[Tuple[str, ...]] = ()
        if len(node.args) >= 3:
            labels = _const_str_list(node.args[2])
        for kw in node.keywords:
            if kw.arg in ("labelnames", "labels"):
                labels = _const_str_list(kw.value)
        if name in out:
            dupes.append((name, node.lineno))
        else:
            out[name] = (kind, labels, node.lineno, None)
    out["__duplicates__"] = dupes  # type: ignore[assignment]
    return out


# ---------------------------------------------------------------- the check
def _check_surface(
    surface: str, extracted: Extracted, relpath: str,
    registry: Tuple[reg.Series, ...],
) -> List[Finding]:
    findings = []
    dupes = extracted.pop("__duplicates__", [])  # type: ignore[arg-type]
    for name, line in dupes:
        findings.append(Finding(
            "PL004", relpath, line,
            f"series {name!r} is declared more than once in this renderer",
        ))
    expected = {s.name: s for s in registry if surface in s.surfaces}
    for name, (kind, labels, line, src_file) in extracted.items():
        where = src_file or relpath
        if not name.startswith(reg.ALLOWED_PREFIXES):
            findings.append(Finding(
                "PL004", where, line,
                f"series {name!r} violates the naming convention (allowed "
                f"prefixes: {', '.join(reg.ALLOWED_PREFIXES)})",
            ))
        entry = expected.get(name)
        if entry is None:
            findings.append(Finding(
                "PL004", where, line,
                f"series {name!r} is not in the metrics registry — add it "
                f"to tools/pstpu_lint/metrics_registry.py and regenerate "
                f"the docs tables (python -m tools.pstpu_lint.gen_docs)",
            ))
            continue
        if entry.kind != kind:
            findings.append(Finding(
                "PL004", where, line,
                f"series {name!r} is a {kind} here but a {entry.kind} in "
                f"the registry",
            ))
        want = entry.labels_for(surface)
        if labels is not None and tuple(labels) != tuple(want):
            findings.append(Finding(
                "PL004", where, line,
                f"series {name!r} label set {tuple(labels)!r} does not "
                f"match the registry ({tuple(want)!r})",
            ))
    for name, entry in expected.items():
        if name not in extracted:
            findings.append(Finding(
                "PL004", relpath, 1,
                f"series {name!r} is in the registry for surface "
                f"{surface!r} but this renderer does not emit it",
            ))
    return findings


def check_metrics(
    project_root: str,
    registry: Optional[Tuple[reg.Series, ...]] = None,
    docs_check: bool = True,
) -> List[Finding]:
    registry = reg.REGISTRY if registry is None else registry
    findings: List[Finding] = []

    def _read(rel):
        with open(os.path.join(project_root, rel), encoding="utf-8") as f:
            return f.read()

    server_src = _read(SERVER_METRICS)
    engine_src = _read(ENGINE_METRICS)
    router_src = _read(ROUTER_METRICS)

    findings += _check_surface(
        reg.ENGINE, extract_engine_text(server_src, engine_src),
        SERVER_METRICS, registry,
    )
    findings += _check_surface(
        reg.ROUTER, extract_router(router_src), ROUTER_METRICS, registry,
    )

    if docs_check:
        from tools.pstpu_lint import gen_docs

        for group, relpath, stale in gen_docs.check_tables(
            project_root, registry=registry
        ):
            findings.append(Finding(
                "PL004", relpath, 1,
                f"docs metrics table {group!r} is {stale}; run "
                f"python -m tools.pstpu_lint.gen_docs",
            ))
    return findings


# ------------------------------------------------------------- registration
def wants(project_root: str) -> bool:
    return all(
        os.path.exists(os.path.join(project_root, p))
        for p in (SERVER_METRICS, ENGINE_METRICS, ROUTER_METRICS)
    )


def check(project_root: str) -> List[Finding]:
    return check_metrics(project_root)
