"""Static lint: no stray device→host readbacks in the hot-path packages.

The one-readback-per-round invariant (ROADMAP, PR 2) is enforced
dynamically by the transfer-guard tests, but those only cover the code
paths the tests happen to drive.  This test covers the rest statically:
every ``block_until_ready`` / ``np.asarray(`` / ``jax.device_get`` in
``src/repro/serving`` and ``src/repro/core`` must sit inside an
explicitly whitelisted function.  Adding a readback anywhere else —
e.g. a well-meaning ``np.asarray`` inside the round loop — fails this
test and forces the author to either move it off the hot path or argue
for a whitelist entry in review.

Comments and strings are stripped (via ``tokenize``) before matching,
so prose mentioning ``device_get`` doesn't trip the lint, and
``jnp.asarray`` (device-side, fine) is excluded by lookbehind.
"""
from __future__ import annotations

import ast
import io
import re
import tokenize
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
SCAN_DIRS = ("serving", "core")

PATTERNS = [re.compile(p) for p in (
    r"block_until_ready",
    r"(?<!j)np\.asarray\(",   # np.asarray but not jnp.asarray
    r"jax\.device_get",
)]

# (file relative to src/repro, function qualname) pairs where a
# device→host sync is deliberate.  Keep this list tight: every entry
# must correspond to a site that is either (a) outside the steady-state
# round loop (warmup, stats, maintenance epochs), (b) the *single*
# sanctioned flag readback, or (c) host-side-only code (baselines,
# cold-tier host folds, client-side input coercion).
ALLOWED = {
    # host-side reference baselines — no device round loop at all
    ("core/baselines.py", "BruteForce.insert"),
    ("core/baselines.py", "BruteForce.query"),
    ("core/baselines.py", "MultiProbeFlat._buckets"),
    ("core/baselines.py", "MultiProbeFlat.insert"),
    ("core/baselines.py", "MultiProbeFlat.query"),
    ("core/baselines.py", "ZOrderIndex._zvals"),
    ("core/baselines.py", "ZOrderIndex.insert"),
    ("core/baselines.py", "ZOrderIndex.query"),
    # cold tier: host folds / spill staging run in maintenance epochs,
    # never inside a steady-state round.  The tiered-store payload
    # moves (vector pages staged at spill, fetched at cold-miss, folded
    # at merge) ride these same entries: spill pulls payload rows in
    # ColdManager.spill, cold-miss fetches install pages via the
    # PFOIndex._query_cold epoch, and merges fold .vec files in
    # ColdManager._merge_cold_impl / _collect — no new sync sites.
    ("core/coldtier.py", "ColdManager._collect"),
    ("core/coldtier.py", "ColdManager._merge_cold_impl"),
    ("core/coldtier.py", "ColdManager.spill"),
    ("core/coldtier.py", "_fold_entries"),
    # index: the sanctioned flag readback + epoch/stat paths
    ("core/index.py", "PFOIndex._merge_with_cold"),
    ("core/index.py", "PFOIndex._query_cold"),
    ("core/index.py", "PFOIndex._read_flags"),
    ("core/index.py", "PFOIndex.fetch_delete_miss"),
    ("core/index.py", "PFOIndex.query"),
    ("core/index.py", "PFOIndex.stats"),
    # serving: result materialization for the caller
    ("serving/engine.py", "ServingEngine._next_token"),
    ("serving/engine.py", "ServingEngine.generate"),
    # distributed cold tier: every site mirrors a whitelisted
    # single-chip counterpart and syncs only on flag-driven epochs or
    # cold-miss rounds, never in a steady-state no-cold-hit round —
    # _spill stages ring payloads host-side (1 sync, like
    # ColdManager.spill), _merge_with_cold drains tombstones + ring for
    # the per-shard host folds (2 syncs, like PFOIndex._merge_with_cold),
    # query_rows picks up the round's single result (+ per-shard fetch
    # masks riding it, like PFOIndex._query_cold), and after_flags
    # services a COLD_MISS delete (like PFOIndex.fetch_delete_miss)
    ("serving/stream.py", "DistBackend._merge_with_cold"),
    ("serving/stream.py", "DistBackend._spill"),
    ("serving/stream.py", "DistBackend.after_flags"),
    ("serving/stream.py", "DistBackend.query_rows"),
    ("serving/stream.py", "DistBackend.ensure_flags"),
    ("serving/stream.py", "DistBackend.read_flags"),
    ("serving/stream.py", "DistBackend.stats"),
    ("serving/stream.py", "DistBackend.warmup"),
    ("serving/stream.py", "LocalBackend.warmup"),
    ("serving/stream.py", "StreamClient.insert"),
    ("serving/stream.py", "StreamClient.query"),
    ("serving/stream.py", "StreamClient.update"),
    ("serving/stream.py", "StreamEngine._query_batch"),
}


def _stripped_lines(path: Path) -> list[str]:
    """Source lines with comments and string literals blanked out."""
    src = path.read_text()
    out = [list(line) for line in src.splitlines(keepends=True)]
    for tok in tokenize.generate_tokens(io.StringIO(src).readline):
        if tok.type in (tokenize.COMMENT, tokenize.STRING):
            (sr, sc), (er, ec) = tok.start, tok.end
            for r in range(sr - 1, er):
                a = sc if r == sr - 1 else 0
                b = ec if r == er - 1 else len(out[r])
                for c in range(a, min(b, len(out[r]))):
                    if out[r][c] not in "\r\n":
                        out[r][c] = " "
    return ["".join(line) for line in out]


def _function_spans(tree: ast.Module) -> list[tuple[int, int, str]]:
    spans: list[tuple[int, int, str]] = []

    def walk(node: ast.AST, prefix: str) -> None:
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = prefix + ch.name
                spans.append((ch.lineno, ch.end_lineno or ch.lineno, q))
                walk(ch, q + ".")
            elif isinstance(ch, ast.ClassDef):
                walk(ch, prefix + ch.name + ".")
            else:
                walk(ch, prefix)

    walk(tree, "")
    return spans


def _scan() -> set[tuple[str, str]]:
    found: set[tuple[str, str]] = set()
    for sub in SCAN_DIRS:
        for path in sorted((SRC / sub).rglob("*.py")):
            lines = _stripped_lines(path)
            spans = _function_spans(ast.parse(path.read_text()))
            rel = str(path.relative_to(SRC))
            for i, line in enumerate(lines, 1):
                if not any(p.search(line) for p in PATTERNS):
                    continue
                qual = "<module>"
                best_start = -1
                for (a, b, name) in spans:
                    if a <= i <= b and a > best_start:
                        best_start, qual = a, name
                found.add((rel, qual))
    return found


def test_no_stray_readbacks():
    found = _scan()
    stray = sorted(found - ALLOWED)
    assert not stray, (
        "device->host readback in non-whitelisted function(s): "
        f"{stray}.  Move it off the hot path or (if deliberate and "
        "outside the steady-state round loop) add it to ALLOWED in "
        f"{__file__} with a justification comment.")


def test_whitelist_has_no_stale_entries():
    found = _scan()
    stale = sorted(ALLOWED - found)
    assert not stale, (
        f"whitelisted readback sites no longer exist: {stale}. "
        "Remove them from ALLOWED so the list stays tight.")
