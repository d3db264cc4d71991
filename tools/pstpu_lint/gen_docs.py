"""Generate the docs metrics tables and README flag tables from the
single-source registries, inside marker comments:

    <!-- pstpu-metrics:BEGIN <group> -->  ...  <!-- pstpu-metrics:END <group> -->
    <!-- pstpu-flags:BEGIN <tier> -->     ...  <!-- pstpu-flags:END <tier> -->
    <!-- pstpu-wire:BEGIN <group> -->     ...  <!-- pstpu-wire:END <group> -->
    <!-- pstpu-http:BEGIN <group> -->     ...  <!-- pstpu-http:END <group> -->

Write mode refreshes the delimited blocks in place; ``--check`` reports
stale/missing blocks without writing (the PL004 rule runs the metrics half
of the check on every lint; PL010 the wire half; PL011-PL013 the http
tables). Sources of truth:

  * series: tools/pstpu_lint/metrics_registry.py
  * flags:  the argparse definitions in router/parser.py and
            server/api_server.py (tools/pstpu_lint/flags.py scans them)
  * wire:   tools/pstpu_lint/wire_registry.py (docs/WIRE_FORMATS.md)
  * http:   tools/pstpu_lint/http_registry.py (docs/HTTP_PROTOCOL.md,
            plus the focused status table in docs/RESILIENCE.md and the
            resume-header table in docs/ROUTER_SCALE.md)

Usage: ``python -m tools.pstpu_lint.gen_docs [--check]``.
"""

import argparse
import os
import re
import sys
from typing import List, Optional, Tuple

from tools.pstpu_lint import metrics_registry as reg
from tools.pstpu_lint.flags import scan_flags

# docs table group -> file carrying its marker block
TABLES = {
    "catalogue": "docs/METRICS.md",
    "dispatch": "docs/PERF.md",
    "disagg": "docs/DISAGG.md",
    "resilience": "docs/RESILIENCE.md",
    "resume": "docs/RESILIENCE.md",
    "autoscaling": "docs/SOAK.md",
    "kv-economy": "docs/KV_ECONOMY.md",
    "speculative": "docs/PERF.md",
    "multichip": "docs/PERF.md",
    "elastic": "docs/ELASTIC.md",
    "lifecycle": "docs/OBSERVABILITY.md",
    "fleet-perf": "docs/OBSERVABILITY.md",
    "loop": "docs/OBSERVABILITY.md",
}

FLAG_TABLES = {
    "router": ("README.md", "production_stack_tpu/router/parser.py"),
    "engine": ("README.md", "production_stack_tpu/server/api_server.py"),
}

# wire table group -> file carrying its marker block (PL010's freshness
# gate, same contract as the PL004 metrics tables above).
WIRE_TABLES = {
    "formats": "docs/WIRE_FORMATS.md",
    "ops": "docs/WIRE_FORMATS.md",
}

# http table group -> file carrying its marker block. The full catalogue
# lives in docs/HTTP_PROTOCOL.md; "status-semantics" and "resume" are the
# focused projections RESILIENCE.md and ROUTER_SCALE.md embed. PL011 owns
# headers/payload/resume freshness, PL012 routes, PL013 the status pair.
HTTP_TABLES = {
    "headers": "docs/HTTP_PROTOCOL.md",
    "routes": "docs/HTTP_PROTOCOL.md",
    "status": "docs/HTTP_PROTOCOL.md",
    "payload": "docs/HTTP_PROTOCOL.md",
    "status-semantics": "docs/RESILIENCE.md",
    "resume": "docs/ROUTER_SCALE.md",
}

_SURFACE_NAMES = {
    reg.ENGINE: "engine /metrics",
    reg.ROUTER: "router /metrics",
}


def render_metrics_table(group: str, registry=None) -> str:
    registry = reg.REGISTRY if registry is None else registry
    lines = [
        "| Series | Type | Labels | Exported by | Meaning |",
        "|---|---|---|---|---|",
    ]
    for s in registry:
        if group not in s.docs:
            continue
        labels = ", ".join(s.labels_for(s.surfaces[0])) or "—"
        exported = ", ".join(_SURFACE_NAMES[x] for x in s.surfaces)
        lines.append(
            f"| `{s.name}` | {s.kind} | {labels} | {exported} "
            f"| {_cell(s.doc)} |"
        )
    return "\n".join(lines)


def _cell(text: str) -> str:
    """Escape raw pipes — inside a markdown table cell they split the row."""
    return text.replace("|", "\\|")


def render_flags_table(parser_source: str) -> str:
    lines = [
        "| Flag | Default | What it does |",
        "|---|---|---|",
    ]
    for flag in scan_flags(parser_source):
        default = flag.default or "—"
        lines.append(
            f"| `{flag.option}` | `{_cell(default)}` | {_cell(flag.help)} |"
        )
    return "\n".join(lines)


def render_wire_table(group: str, formats=None, ops=None) -> str:
    from tools.pstpu_lint import wire_registry as wreg

    formats = wreg.FORMATS if formats is None else formats
    ops = wreg.OPS if ops is None else ops
    if group == "formats":
        lines = [
            "| Magic | Family | Version | Supersedes | Status | Meaning |",
            "|---|---|---|---|---|---|",
        ]
        for f in formats:
            status = "retired" if f.retired else "current"
            lines.append(
                f"| `{f.magic}` | {f.family} | v{f.version} "
                f"| {f.supersedes or '—'} | {status} | {_cell(f.doc)} |"
            )
        return "\n".join(lines)
    lines = [
        "| Op | Name | Batched | Mutates | Native server | Meaning |",
        "|---|---|---|---|---|---|",
    ]
    for o in ops:
        native = "yes" if o.native else "no (STATUS_ERROR; client degrades)"
        lines.append(
            f"| `{o.op}` | {o.name} | {'yes' if o.batched else 'no'} "
            f"| {'yes' if o.mutates else 'no'} | {native} "
            f"| {_cell(o.doc)} |"
        )
    return "\n".join(lines)


def render_http_table(group: str, headers=None, routes=None,
                      statuses=None) -> str:
    from tools.pstpu_lint import http_registry as hreg

    headers = hreg.HEADERS if headers is None else headers
    routes = hreg.ROUTES if routes is None else routes
    statuses = hreg.STATUS_CODES if statuses is None else statuses
    if group == "headers":
        lines = [
            "| Header | Direction | Producers | Consumers | Value "
            "| Status | Meaning |",
            "|---|---|---|---|---|---|---|",
        ]
        for h in headers:
            lines.append(
                f"| `{h.name}` | {h.direction} "
                f"| {', '.join(h.producers)} | {', '.join(h.consumers)} "
                f"| {_cell(h.shape)} "
                f"| {'retired' if h.retired else 'active'} "
                f"| {_cell(h.doc)} |")
        return "\n".join(lines)
    if group == "routes":
        lines = [
            "| Method | Path | Planes | Debug-gated | Internal "
            "| Meaning |",
            "|---|---|---|---|---|---|",
        ]
        for r in routes:
            lines.append(
                f"| {r.method} | `{r.path}` | {', '.join(r.planes)} "
                f"| {'yes' if r.debug else 'no'} "
                f"| {'yes' if r.internal else 'no'} | {_cell(r.doc)} |")
        return "\n".join(lines)
    if group in ("status", "status-semantics"):
        lines = [
            "| Code | Type | Required response headers | Server-emitted "
            "| Meaning |",
            "|---|---|---|---|---|",
        ]
        for s in statuses:
            companions = ", ".join(
                f"`{c}`" for c in s.companions) or "—"
            emitted = "yes" if s.server_emitted else "**never**"
            lines.append(
                f"| {s.code} | `{s.name}` | {companions} | {emitted} "
                f"| {_cell(s.doc)} |")
        return "\n".join(lines)
    if group == "payload":
        lines = [
            "| Key | Type | Meaning |",
            "|---|---|---|",
        ]
        for k in hreg.SSE_PAYLOAD_KEYS:
            lines.append(f"| `{k.key}` | {k.shape} | {_cell(k.doc)} |")
        return "\n".join(lines)
    # "resume": the client->router cross-router resume header pair
    # ROUTER_SCALE.md documents next to the reconnect walkthrough.
    lines = [
        "| Header | Value | Meaning |",
        "|---|---|---|",
    ]
    for h in headers:
        if h.name.startswith("x-pstpu-resume-"):
            lines.append(
                f"| `{h.name}` | {_cell(h.shape)} | {_cell(h.doc)} |")
    return "\n".join(lines)


def _block_re(kind: str, group: str) -> re.Pattern:
    return re.compile(
        rf"(<!-- pstpu-{kind}:BEGIN {re.escape(group)} -->)\n"
        rf"(.*?)"
        rf"(<!-- pstpu-{kind}:END {re.escape(group)} -->)",
        re.S,
    )


def _update_block(text: str, kind: str, group: str,
                  table: str) -> Optional[str]:
    """New file text with the block replaced, or None if markers absent."""
    pat = _block_re(kind, group)
    if pat.search(text) is None:
        return None
    return pat.sub(
        lambda m: m.group(1) + "\n" + table + "\n" + m.group(3),
        text, count=1,
    )


def _iter_blocks(project_root: str, registry=None, kinds=None,
                 wire_registries=None, http_registries=None,
                 http_groups=None):
    """Every generated block as (kind, group, relpath, path, table-or-None);
    table is None when an input file is missing. ``kinds`` restricts which
    table families are rendered (PL004 checks only the metrics tables,
    PL006 only the flag tables — no point rendering the other half);
    ``http_groups`` further restricts the http family (each of
    PL011-PL013 owns a subset of its tables)."""
    if kinds is None or "metrics" in kinds:
        for group, relpath in TABLES.items():
            path = os.path.join(project_root, relpath)
            table = (render_metrics_table(group, registry)
                     if os.path.exists(path) else None)
            yield "metrics", group, relpath, path, table
    if kinds is None or "flags" in kinds:
        for tier, (relpath, parser_rel) in FLAG_TABLES.items():
            path = os.path.join(project_root, relpath)
            parser_path = os.path.join(project_root, parser_rel)
            table = None
            if os.path.exists(path) and os.path.exists(parser_path):
                with open(parser_path, encoding="utf-8") as f:
                    table = render_flags_table(f.read())
            yield "flags", tier, relpath, path, table
    if kinds is None or "wire" in kinds:
        for group, relpath in WIRE_TABLES.items():
            path = os.path.join(project_root, relpath)
            table = (render_wire_table(group, **(wire_registries or {}))
                     if os.path.exists(path) else None)
            yield "wire", group, relpath, path, table
    if kinds is None or "http" in kinds:
        for group, relpath in HTTP_TABLES.items():
            if http_groups is not None and group not in http_groups:
                continue
            path = os.path.join(project_root, relpath)
            table = (render_http_table(group, **(http_registries or {}))
                     if os.path.exists(path) else None)
            yield "http", group, relpath, path, table


def _sync_blocks(project_root: str, registry=None,
                 write: bool = False,
                 kinds=None,
                 wire_registries=None,
                 http_registries=None,
                 http_groups=None) -> List[Tuple[str, str, str]]:
    """One pass over every block. write=False: report (group, relpath,
    problem) per stale/missing block. write=True: refresh stale blocks in
    place and report (group, relpath, "updated") per file written —
    missing files/markers are reported identically in both modes, so
    ``gen_docs`` and ``gen_docs --check`` can never disagree on a tree."""
    out = []
    for kind, group, relpath, path, table in _iter_blocks(
        project_root, registry, kinds, wire_registries,
        http_registries, http_groups
    ):
        if table is None:
            out.append((group, relpath, "missing (file not found)"))
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        updated = _update_block(text, kind, group, table)
        if updated is None:
            out.append((group, relpath, "missing its marker block"))
        elif updated != text:
            if write:
                with open(path, "w", encoding="utf-8") as f:
                    f.write(updated)
                out.append((group, relpath, "updated"))
            else:
                out.append((group, relpath, "out of date"))
    return out


def check_tables(project_root: str,
                 registry=None) -> List[Tuple[str, str, str]]:
    """(group, relpath, problem) for every stale/missing metrics block."""
    return _sync_blocks(project_root, registry, kinds={"metrics"})


def check_flag_tables(project_root: str) -> List[Tuple[str, str, str]]:
    return _sync_blocks(project_root, kinds={"flags"})


def check_wire_tables(project_root: str, formats=None,
                      ops=None) -> List[Tuple[str, str, str]]:
    """(group, relpath, problem) for every stale/missing wire block
    (the PL010 docs-freshness gate)."""
    wire = None
    if formats is not None or ops is not None:
        wire = {"formats": formats, "ops": ops}
    return _sync_blocks(project_root, kinds={"wire"}, wire_registries=wire)


def check_http_tables(project_root: str, groups=None, headers=None,
                      routes=None, statuses=None
                      ) -> List[Tuple[str, str, str]]:
    """(group, relpath, problem) for every stale/missing http block
    (the PL011-PL013 docs-freshness gates; ``groups`` restricts to the
    calling rule's tables)."""
    http = None
    if headers is not None or routes is not None or statuses is not None:
        http = {"headers": headers, "routes": routes,
                "statuses": statuses}
    return _sync_blocks(project_root, kinds={"http"},
                        http_registries=http, http_groups=groups)


def write_tables(project_root: str) -> List[str]:
    """Refresh every block in place; returns the files touched (and raises
    nothing on missing files — they surface via --check / PL004)."""
    return [relpath for _g, relpath, what in _sync_blocks(
        project_root, write=True) if what == "updated"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tools.pstpu_lint.gen_docs",
        description="Regenerate docs metrics tables + README flag tables "
                    "from the registries.",
    )
    p.add_argument("--check", action="store_true",
                   help="report stale blocks without writing (exit 1)")
    p.add_argument("--project-root", default=".")
    args = p.parse_args(argv)
    root = os.path.abspath(args.project_root)
    if args.check:
        problems = (check_tables(root) + check_flag_tables(root)
                    + check_wire_tables(root) + check_http_tables(root))
        for group, relpath, what in problems:
            print(f"{relpath}: table {group!r} is {what}", file=sys.stderr)
        return 1 if problems else 0
    for relpath in write_tables(root):
        print(f"updated {relpath}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
