"""Source hygiene + policy gates — the reference's CI lint tier (testing/
test_flake8.py, test_jsonnet.py) re-built on stdlib ``ast`` since the image
ships no flake8: every Python source must parse, carry no unused imports,
and no `except:` bare handlers. Runs over the package, e2e harness, ci
builders, and bench entrypoints.

The AST scaffolding (file walker, qualname stack, constant-call scanner)
lives in ``tools/platlint/core.py``, shared with the platlint analyzer —
which also runs here as a tier-1 gate (see ``test_platlint_tree_is_clean``
and docs/STATIC_ANALYSIS.md).
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

import pytest

from tools.platlint import run_gate
from tools.platlint.core import (REPO_ROOT, QualnameVisitor,
                                 constant_call_names, python_sources)

ROOT = REPO_ROOT

SOURCES = list(python_sources())
IDS = [str(p.relative_to(ROOT)) for p in SOURCES]


class ImportAudit(ast.NodeVisitor):
    """Collect imported top-level names and every name/attribute root used."""

    def __init__(self) -> None:
        self.imported: dict[str, int] = {}
        self.used: set[str] = set()
        self.exported: set[str] = set()

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            self.imported[name] = node.lineno

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return
        for alias in node.names:
            if alias.name == "*":
                continue
            name = alias.asname or alias.name
            self.imported[name] = node.lineno

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        # __all__ = [...] re-exports count as uses
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id == "__all__":
                for elt in getattr(node.value, "elts", []):
                    if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                        self.exported.add(elt.value)
        self.generic_visit(node)


@pytest.mark.parametrize("path", SOURCES, ids=IDS)
def test_source_hygiene(path: Path):
    src = path.read_text()
    tree = ast.parse(src, filename=str(path))  # syntax gate

    # bare except (swallows KeyboardInterrupt/SystemExit)
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            pytest.fail(f"{path}:{node.lineno}: bare `except:`")

    # unused imports — re-export files (__init__.py) use imports as surface
    audit = ImportAudit()
    audit.visit(tree)
    if path.name == "__init__.py":
        return
    # string-annotation and doctest references are rare here; noqa escape:
    lines = src.splitlines()
    unused = []
    for name, lineno in audit.imported.items():
        if name in audit.used or name in audit.exported or name == "_":
            continue
        line = lines[lineno - 1] if lineno - 1 < len(lines) else ""
        if "noqa" in line:
            continue
        # names referenced only inside string type annotations
        if f'"{name}' in src or f"'{name}" in src:
            continue
        unused.append(f"{path}:{lineno}: unused import {name!r}")
    assert not unused, "\n".join(unused)


# -- platlint: lock discipline & deadlock order --------------------------------
#
# The full analyzer (guarded-field inference, lock-order graph,
# blocking-under-lock) runs as a tier-1 gate. New findings either get fixed
# or get a reason-annotated entry in tools/platlint/baseline.json; fixing a
# baselined finding requires deleting its entry (stale entries fail too).

PLATLINT_BASELINE = ROOT / "tools" / "platlint" / "baseline.json"


def test_platlint_tree_is_clean():
    result = run_gate([Path("kubeflow_tpu")], baseline=PLATLINT_BASELINE)
    problems = [f.render() for f in result.new]
    problems += [f"stale baseline entry: {s}" for s in result.stale]
    assert result.ok, (
        "platlint gate failed (see docs/STATIC_ANALYSIS.md; reproduce with "
        "`python -m tools.platlint kubeflow_tpu`):\n" + "\n".join(problems)
    )


def _node_name_writes(tree: ast.AST):
    """AST sites that set ``nodeName``: subscript assigns
    (``pod["spec"]["nodeName"] = ...``) and dict literals carrying a
    ``"nodeName"`` key (``spec={"nodeName": ...}``)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.slice, ast.Constant)
                    and t.slice.value == "nodeName"
                ):
                    yield node.lineno
        elif isinstance(node, ast.Dict):
            for k in node.keys:
                if isinstance(k, ast.Constant) and k.value == "nodeName":
                    yield node.lineno


def test_binding_authority_stays_in_scheduler():
    """Pod→node binding has exactly one writer: the scheduler subsystem.

    Any other component mutating ``spec.nodeName`` (the pre-split podlet
    did) reintroduces split-brain placement — capacity accounting, gang
    all-or-nothing semantics, and preemption all assume the scheduler's
    ledger sees every bind. Reads (``spec.get("nodeName")``) stay free.
    """
    scheduler_dir = ROOT / "kubeflow_tpu" / "scheduler"
    offenders = []
    for path in SOURCES:
        if scheduler_dir in path.parents:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        offenders.extend(
            f"{path.relative_to(ROOT)}:{lineno}: writes spec.nodeName"
            for lineno in _node_name_writes(tree)
        )
    assert not offenders, (
        "only kubeflow_tpu/scheduler/ may bind pods to nodes:\n" + "\n".join(offenders)
    )


# -- dtype gate: bf16 matmuls in model forward passes -------------------------
#
# The MFU work (BASELINE rounds 4-5) hinges on every matmul/conv feeding the
# MXU bf16 inputs; one stray f32 contraction halves throughput silently. The
# sanctioned fp32 islands are numerics-critical and stay: losses, attention
# softmax, and the final logits/classifier head.
F32_MATMUL_ALLOWLIST = {
    ("gpt.py", "GptAttention._decode_attention"),  # decode softmax island
    ("gpt.py", "GptAttention._paged_decode_attention"),  # same island, paged
    ("gpt.py", "GptLM.__call__"),                  # f32 logits head
    ("gpt.py", "causal_lm_loss"),
    ("gpt.py", "blockwise_causal_lm_loss"),
    # bf16 operands, float32 ACCUMULATION (preferred_element_type): the
    # decode softmax island and the float32 logits head of the MiMo family
    ("mimo.py", "_decode_attention"),
    ("mimo.py", "decode_step"),
    ("mimo.py", "prefill_chunk"),
    # the EvaByte family: the float32 logits head (bf16 operands, float32
    # accumulation), and a chunk's pooling into its summary, which ISSUE 32
    # states in float32 (16 keys a chunk: a thousandth of a layer's work)
    ("evabyte.py", "_head"),
    ("evabyte.py", "summarise"),
    # the SDAR family: the float32 logits head (bf16 operands, float32
    # accumulation); its router's float32 product lives in parallel/moe.py
    ("sdar.py", "_head"),
}

_MATMUL_CALLEES = {"einsum", "matmul", "dot", "tensordot", "dot_general"}


def _mentions_f32(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == "float32":
            return True
        if isinstance(n, ast.Constant) and n.value == "float32":
            return True
    return False


class _F32MatmulFinder(QualnameVisitor):
    """(qualname, lineno) of every matmul-family op (einsum/matmul/dot/
    dot_general/``@``) whose expression mentions float32. Scope tracking
    comes from the shared QualnameVisitor."""

    def __init__(self) -> None:
        super().__init__()
        self.hits: list[tuple[str, int]] = []

    def _check(self, node: ast.AST) -> None:
        if _mentions_f32(node):
            self.hits.append((self.qualname, node.lineno))

    def visit_BinOp(self, node: ast.BinOp) -> None:
        if isinstance(node.op, ast.MatMult):
            self._check(node)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else getattr(fn, "id", None)
        if name in _MATMUL_CALLEES:
            self._check(node)
        self.generic_visit(node)


def test_no_f32_matmuls_outside_sanctioned_islands():
    """Model forward passes keep matmul/einsum inputs bf16; fp32 appears
    only in the allowlisted islands above. A new f32 contraction must either
    become bf16 or be explicitly added here with a numerics justification."""
    models_dir = ROOT / "kubeflow_tpu" / "models"
    offenders = []
    for path in sorted(models_dir.glob("*.py")):
        finder = _F32MatmulFinder()
        finder.visit(ast.parse(path.read_text(), filename=str(path)))
        allowed = {q for f, q in F32_MATMUL_ALLOWLIST if f == path.name}
        for qual, lineno in finder.hits:
            if any(qual == a or qual.startswith(a + ".") for a in allowed):
                continue
            offenders.append(
                f"{path.relative_to(ROOT)}:{lineno}: f32 matmul in {qual}")
    assert not offenders, (
        "f32 matmul outside the sanctioned fp32 islands (make it bf16 or "
        "extend F32_MATMUL_ALLOWLIST with justification):\n" + "\n".join(offenders)
    )


# -- metric-catalog gate: every metric name must be documented ----------------
#
# docs/OBSERVABILITY.md is the catalog of record for the observability plane.
# A metric registered in code but absent there is invisible to operators and
# rots the moment someone renames it — so the catalog is lint-enforced. Both
# catalog gates are one constant_call_names() query over the package.

_METRIC_METHODS = {"counter", "gauge", "histogram", "timer"}
# ``annotate`` regions (tpu/profiling.py: the profiler's trace, not the
# Tracer's ring) are named in the same catalog
_SPAN_METHODS = {"span", "start_span", "emit_span", "annotate"}

PKG_SOURCES = [p for p in SOURCES if (ROOT / "kubeflow_tpu") in p.parents]


def _registered_metric_names():
    """(name, namespace prefixes in the file, path, lineno) for every
    constant-name metric registration under kubeflow_tpu/. f-string and
    variable names (StepClock's ``step_{name}_seconds``, note() gauges)
    have no constant to check and are skipped — the catalog documents
    their patterns prose-side instead."""
    for path in PKG_SOURCES:
        tree = ast.parse(path.read_text(), filename=str(path))
        prefixes = set()
        calls = []
        for method, name, lineno in constant_call_names(
                tree, _METRIC_METHODS | {"namespace"}):
            if method == "namespace":
                prefixes.add(name)
            else:
                calls.append((name, lineno))
        for name, lineno in calls:
            yield name, prefixes, path, lineno


def test_metric_names_are_cataloged():
    catalog = (ROOT / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(re.findall(r"`([A-Za-z_:][A-Za-z0-9_:]*)`", catalog))
    missing = []
    for name, prefixes, path, lineno in _registered_metric_names():
        candidates = {name} | {f"{p}_{name}" for p in prefixes}
        if not candidates & documented:
            missing.append(
                f"{path.relative_to(ROOT)}:{lineno}: metric {name!r} "
                "not documented in docs/OBSERVABILITY.md")
    assert not missing, (
        "add these metrics to the docs/OBSERVABILITY.md catalog "
        "(name, type, labels, meaning):\n" + "\n".join(missing)
    )


def test_span_names_are_cataloged():
    """docs/OBSERVABILITY.md is the catalog of record for span names too:
    federated traces are only navigable if the names that appear in an
    assembled gang-bind journey mean something to the reader. Dynamic
    names (StepClock's per-step emits, f-strings) have no constant to
    check and are skipped, same policy as metrics."""
    catalog = (ROOT / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(re.findall(r"`([A-Za-z0-9_.]+)`", catalog))
    missing = []
    for path in PKG_SOURCES:
        tree = ast.parse(path.read_text(), filename=str(path))
        for _method, name, lineno in constant_call_names(tree, _SPAN_METHODS):
            if name not in documented:
                missing.append(
                    f"{path.relative_to(ROOT)}:{lineno}: span {name!r} "
                    "not documented in docs/OBSERVABILITY.md")
    assert not missing, (
        "add these span names to the docs/OBSERVABILITY.md catalog "
        "(name, emitting process, parent, meaning):\n" + "\n".join(missing)
    )
