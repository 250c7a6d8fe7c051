"""obs-catalog-drift: both directions, brace expansion, patterns."""

import textwrap

from realhf_tpu.analysis.obs_catalog import (
    ObsCatalogChecker,
    expand_doc_token,
    parse_catalog,
)

DOC = """\
# Observability

| Question | Piece |
|---|---|
| irrelevant | `not_a_metric_table` |

### Catalog

| Metric | Type | Source |
|---|---|---|
| `a_total` | counter | somewhere |
| `serving_{x,y}_total` | counter | expansion |
| `latency_secs{server}` | summary | labels dropped |
| `stale_total` | counter | nothing emits this |
| `dyn_q_total` | counter | spelled dynamically in code |

### Exports

| Path | Content |
|---|---|
| `GET /metrics` | not metric names |
"""

CODE = """\
from realhf_tpu.obs import metrics

def instrument(k):
    metrics.inc("a_total")
    metrics.inc("serving_x_total")
    metrics.inc("serving_y_total")
    metrics.observe("latency_secs", 0.1, server="s")
    metrics.inc("undocumented_total")
    metrics.inc(f"dyn_{k}_total")
    metrics.inc(k)  # fully dynamic: out of scope
"""


def seed(tmp_path, doc=DOC, code=CODE):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(doc)
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "mod.py").write_text(code)
    return ObsCatalogChecker(package="pkg")


# ----------------------------------------------------------------------
def test_expand_doc_token():
    assert expand_doc_token("a_total") == {"a_total"}
    assert expand_doc_token("serving_{x,y}_total") == {
        "serving_x_total", "serving_y_total"}
    assert expand_doc_token("watchdog_workers{state}") == {
        "watchdog_workers"}
    assert expand_doc_token("mfc_exec_secs{mfc,worker}") == {
        "mfc_exec_secs"}
    assert expand_doc_token(
        "router_{requests,terminals{kind},expired}_total") == {
        "router_requests_total", "router_terminals_total",
        "router_expired_total"}
    assert expand_doc_token("GET /metrics") == set()


def test_parse_catalog_scopes_to_the_catalog_section():
    names = parse_catalog(DOC)
    assert "a_total" in names and "serving_x_total" in names
    assert "not_a_metric_table" not in names
    assert "latency_secs" in names


def test_both_drift_directions(tmp_path):
    checker = seed(tmp_path)
    fs = checker.check_project(str(tmp_path))
    by_code = {(f.path, f.message.split("`")[1]) for f in fs}
    assert all(f.code == "obs-catalog-drift" for f in fs)
    # code -> doc: the undocumented metric, at its call site
    assert ("pkg/mod.py", "undocumented_total") in by_code
    # doc -> code: the stale row, at the doc line
    assert ("docs/observability.md", "stale_total") in by_code
    # the dynamically-spelled name is excused by the f-string pattern
    assert all("dyn_q_total" not in f.message for f in fs)
    assert len(fs) == 2


def test_a_name_handed_to_a_table_is_an_emit_site(tmp_path):
    """``Record(token_counter="x_total")``: the record names the counter its
    caller feeds (``metrics.inc(rec.token_counter, ...)``), both directions."""
    code = CODE + 'TABLE = dict(a=Record(token_counter="stale_total"),\n' \
        '             b=Record(token_counter="unlisted_total", o="a_total"))\n'
    fs = seed(tmp_path, code=code).check_project(str(tmp_path))
    assert {f.message.split("`")[1] for f in fs} == {
        "undocumented_total", "unlisted_total"}
    [f] = [f for f in fs if "unlisted_total" in f.message]
    assert (f.path, f.line) == ("pkg/mod.py", len(code.splitlines()))


def test_clean_tree_and_missing_doc(tmp_path):
    checker = seed(tmp_path, doc=DOC.replace(
        "| `stale_total` | counter | nothing emits this |\n", ""),
        code=CODE.replace(
            '    metrics.inc("undocumented_total")\n', ""))
    assert checker.check_project(str(tmp_path)) == []
    # fixture trees without the doc produce nothing (never guess)
    empty = ObsCatalogChecker(package="nope")
    assert empty.check_project(str(tmp_path)) == []


def test_stamp_extra_tracks_the_doc(tmp_path):
    checker = seed(tmp_path)
    s1 = checker.stamp_extra(str(tmp_path))
    (tmp_path / "docs" / "observability.md").write_text(DOC + "\nx")
    assert checker.stamp_extra(str(tmp_path)) != s1


def test_repo_catalog_parses_real_rows():
    """Smoke-test the expansion rules against the real doc (the
    repo-wide gate depends on them)."""
    import os
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", ".."))
    with open(os.path.join(root, "docs", "observability.md")) as f:
        names = parse_catalog(f.read())
    for expected in ("master_steps_total", "serving_prefills_total",
                     "router_terminals_total", "serve_request_seconds",
                     "agentic_episodes_total"):
        assert expected in names, expected
