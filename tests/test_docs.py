"""Documentation health: every local markdown link resolves, the docs the
README promises exist, and the CLI reference covers every prune flag.

Cheap (no jax import in the subprocess): keeps docs inside the tier-1 gate
so a file move that orphans README/docs links fails the suite, not just the
CI docs job.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_markdown_links_resolve():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_docs.py"), ROOT],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, f"broken docs links:\n{r.stdout}{r.stderr}"


def test_readme_and_docs_exist():
    for rel in ("README.md", "docs/calibration.md", "docs/cli.md",
                "docs/kernels.md", "docs/roofline.md", "docs/pipeline.md",
                "docs/serving.md", "ROADMAP.md", "PAPER.md"):
        assert os.path.exists(os.path.join(ROOT, rel)), rel


def test_readme_links_serving_doc():
    """The serving-engine design doc must stay reachable from the README
    (acceptance criterion of the continuous-batching PR)."""
    readme = open(os.path.join(ROOT, "README.md"), encoding="utf-8").read()
    assert "docs/serving.md" in readme


def test_readme_links_pipeline_doc():
    """The one-traversal design doc must stay reachable from the README
    (acceptance criterion of the speculative-calibration PR)."""
    readme = open(os.path.join(ROOT, "README.md"), encoding="utf-8").read()
    assert "docs/pipeline.md" in readme


def test_pipeline_doc_carries_hit_rate_table():
    """docs/pipeline.md must contain the margin-vs-hit-rate experiment
    table and label it for what it is: a record made on reduced configs,
    on the CPU, with no script left to regenerate it."""
    doc = open(os.path.join(ROOT, "docs", "pipeline.md"),
               encoding="utf-8").read()
    assert "| arch | margin | candidates/keep | hit-rate |" in doc
    assert "--one-traversal" in doc
    assert "recorded on reduced configs, on the CPU" in doc
    assert "no script" in doc


def _cli_flags(module):
    src = open(os.path.join(ROOT, "src", "repro", "launch",
                            f"{module}.py"), encoding="utf-8").read()
    flags = set(re.findall(r'add_argument\("(--[a-z0-9-]+)"', src))
    assert flags, f"no flags parsed from launch/{module}.py"
    return flags


def _prune_flags():
    return _cli_flags("prune")


def _serve_flags():
    return _cli_flags("serve")


def test_cli_doc_covers_every_prune_flag():
    """docs/cli.md must document every --flag launch/prune.py defines (so a
    new flag without docs fails here, not in review)."""
    flags = _prune_flags()
    doc = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    missing = {f for f in flags if f"`{f}`" not in doc}
    assert not missing, f"flags undocumented in docs/cli.md: {sorted(missing)}"


def test_cli_doc_covers_every_serve_flag():
    """Same coverage direction for the serving CLI: every --flag
    launch/serve.py defines (--trace, --slots, ...) must be documented."""
    flags = _serve_flags()
    doc = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    missing = {f for f in flags if f"`{f}`" not in doc}
    assert not missing, f"flags undocumented in docs/cli.md: {sorted(missing)}"


def _table_flags(rel):
    """`--flag` tokens in the first column of ``rel``'s markdown tables."""
    documented = set()
    for line in open(os.path.join(ROOT, rel), encoding="utf-8"):
        if line.startswith("|"):
            documented |= set(re.findall(r"`(--[a-z0-9-]+)`",
                                         line.split("|")[1]))
    return documented


def test_cli_doc_has_no_stale_flags():
    """The reverse direction: every `--flag` docs/cli.md's Flags tables
    document must still exist in launch/prune.py or launch/serve.py —
    catches renamed or removed flags leaving stale docs behind (the
    --rank-policy drift class fixed in PR 2)."""
    flags = _prune_flags() | _serve_flags()
    documented = _table_flags("docs/cli.md")
    assert documented, "no flags parsed from docs/cli.md's table"
    stale = documented - flags
    assert not stale, f"docs/cli.md documents removed flags: {sorted(stale)}"


def test_pipeline_doc_has_no_stale_prune_flags():
    """Same stale-flag reverse check for docs/pipeline.md: any launch flag
    its tables lead with must still exist in launch/prune.py, so the
    one-traversal narrative can't drift from the CLI it describes."""
    stale = _table_flags("docs/pipeline.md") - _prune_flags()
    assert not stale, \
        f"docs/pipeline.md documents removed flags: {sorted(stale)}"


def test_one_traversal_flags_documented():
    """The speculative-calibration flags must exist in the CLI and be
    documented (belt-and-braces on top of the generic coverage check)."""
    flags = _prune_flags()
    assert {"--one-traversal", "--spec-margin"} <= flags
    doc = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    for f in ("--one-traversal", "--spec-margin"):
        assert f"`{f}`" in doc, f


def test_frontend_flags_documented():
    """The serving front-end flags must exist in the CLI and be documented
    in cli.md AND covered by serving.md's Front-end section (belt-and-
    braces on top of the generic coverage check)."""
    flags = _serve_flags()
    frontend = {"--queue-depth", "--deadline-ms", "--deadline-frac",
                "--prefix-cache", "--prefix-len", "--spf"}
    assert frontend <= flags, sorted(frontend - flags)
    cli = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    for f in sorted(frontend):
        assert f"`{f}`" in cli, f
    serving = open(os.path.join(ROOT, "docs", "serving.md"),
                   encoding="utf-8").read()
    assert "## Front-end" in serving
    for needle in ("Overloaded", "queue-depth", "prefix cache", "deadline"):
        assert needle in serving, needle


def test_zoo_serving_flags_documented():
    """The config-zoo serving flags must exist in their CLIs and be
    documented in cli.md, and serving.md must carry the slot-cache
    contracts section the zoo matrix and engine dispatch rely on
    (belt-and-braces on top of the generic two-direction coverage)."""
    assert "--expert-sparsity" in _prune_flags()
    assert {"--expert-sparsity", "--mem-len"} <= _serve_flags()
    cli = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    for f in ("--expert-sparsity", "--mem-len"):
        assert f"`{f}`" in cli, f
    serving = open(os.path.join(ROOT, "docs", "serving.md"),
                   encoding="utf-8").read()
    assert "## Slot-cache contracts" in serving
    for needle in ("recurrent", "mem_len", "expert", "cache_contract",
                   "errors.py"):
        assert needle in serving, needle


def test_sharded_serving_flags_documented():
    """The mesh-sharded serving flags must exist in the CLI and be
    documented in cli.md, and serving.md must carry the Mesh-sharded
    serving section with the leaf placement table, the scatter-admit
    soundness argument, the footprint math, and the name of the test that
    holds the footprint claim (belt-and-braces on top of the generic
    two-direction coverage)."""
    assert {"--mesh-shape", "--serve-sharded"} <= _serve_flags()
    cli = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    for f in ("--mesh-shape", "--serve-sharded"):
        assert f"`{f}`" in cli, f
    serving = open(os.path.join(ROOT, "docs", "serving.md"),
                   encoding="utf-8").read()
    assert "## Mesh-sharded serving" in serving
    for needle in ("slot_specs", "shard_ineligible", "scatter",
                   "device_bytes_estimate", "replicated", "eff_qk",
                   "test_sharded_engine_token_parity_and_leaf_placement",
                   "all-or-nothing"):
        assert needle in serving, needle


def test_scheduler_flags_documented():
    """The scheduler's chunked-prefill flag must exist in the CLI and be
    documented in cli.md, and serving.md must carry the Scheduler section
    with the layer diagram, the chunk-interleaving exactness argument,
    and the per-contract eligibility table (belt-and-braces on top of
    the generic two-direction coverage), and the name of the test that
    holds the interleaving claim."""
    assert "--prefill-chunk" in _serve_flags()
    cli = open(os.path.join(ROOT, "docs", "cli.md"), encoding="utf-8").read()
    assert "`--prefill-chunk`" in cli
    serving = open(os.path.join(ROOT, "docs", "serving.md"),
                   encoding="utf-8").read()
    assert "## Scheduler" in serving
    for needle in ("begin_admit", "continue_admit", "PREFILLING",
                   "byte-identical", "chunk-eligible", "chunk_invalid",
                   "chunk_unsupported", "write_slot",
                   "test_chunked_prefill_interleaves_with_decode"):
        assert needle in serving, needle


def test_readme_documents_subprocess_marker():
    """README must explain deselecting the environment-sensitive
    subprocess tests (`-m "not subprocess"`)."""
    readme = open(os.path.join(ROOT, "README.md"), encoding="utf-8").read()
    assert "not subprocess" in readme
