"""Invariant lint engine (constdb_tpu/analysis): the corpus fires every
rule, the escape hatch + baseline machinery work, and the LIVE TREE is
clean against the committed baseline — the tier-1 gate that keeps the
async/stage/shard disciplines from regressing."""

import os

import pytest

from constdb_tpu import conf
from constdb_tpu.analysis import (ALL_RULES, analyze_paths,
                                  check_readme_registry,
                                  compare_to_baseline, load_baseline,
                                  run_default_analysis)
from constdb_tpu.analysis.__main__ import main as lint_main

CORPUS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "analysis_corpus")


@pytest.fixture(scope="module")
def corpus_findings():
    return analyze_paths([CORPUS], root=CORPUS)


# ------------------------------------------------------------- the corpus

def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


def test_every_rule_has_corpus(corpus_findings):
    """A rule without a seeded violation is a rule nobody knows works."""
    fired = {f.rule for f in corpus_findings}
    for rule in ALL_RULES:
        assert rule.name in fired, \
            f"{rule.name} has no firing snippet under tests/analysis_corpus"


def test_corpus_expectations(corpus_findings):
    by = _by_rule(corpus_findings)
    # ASYNC-BLOCK: sleep + socket + open + .result() + nested-helper open
    ab = by["ASYNC-BLOCK"]
    assert len(ab) == 5
    assert {f.token for f in ab} == \
        {"time.sleep", "socket.socket", "open", ".result()"}
    assert any("nested" in f.qualname for f in ab)
    # STAGE-PURE: 2 device touches + jax name in stages, 2 heavy calls
    # in dispatch
    sp = by["STAGE-PURE"]
    assert {f.token for f in sp} == \
        {"self._put_batch", "self._jax", "jax", "np.stack",
         "self._combine_groups"}
    # CHECK-THEN-MUTATE: raise-after-mutate + assert-after-append only
    cm = by["CHECK-THEN-MUTATE"]
    assert sorted(f.token for f in cm) == ["assert", "raise"]
    assert all("fixed" not in f.qualname for f in cm)
    # ENV-REGISTRY: direct get, subscript, unregistered helper name
    er = by["ENV-REGISTRY"]
    assert {f.token for f in er} == \
        {"CONSTDB_SECRET_KNOB", "CONSTDB_OTHER_KNOB",
         "CONSTDB_NOT_IN_REGISTRY:unregistered"}
    # SHM-LIFECYCLE: only the unguarded creation (guarded ok, ignore
    # comment honored on the transferred one)
    sh = by["SHM-LIFECYCLE"]
    assert [f.qualname.rsplit(".", 1)[-1] for f in sh] == ["leaky"]
    # BARE-EXCEPT-SWALLOW: the apply path only (narrow + __del__ exempt)
    be = by["BARE-EXCEPT-SWALLOW"]
    assert [f.qualname for f in be] == ["apply_frames"]
    # FORK-CAPTURE: lambda, closure, bound method, self.engine, engine
    fc = by["FORK-CAPTURE"]
    assert all(f.qualname.endswith("spawn_bad") for f in fc)
    assert {f.token for f in fc} == \
        {"lambda", "closure_worker", "self.run_shard", "self.engine",
         "engine"}
    # KEY-CONFINED: second-arg key + underivable key; the clean command
    # and the delegating helper stay silent
    kc = by["KEY-CONFINED"]
    assert {f.token for f in kc} == {"badswap", "nokey"}
    assert not any("good" in f.qualname for f in kc)
    # NATIVE-CONTRACT: the uncovered @serve_plan command (intake
    # direction) + every aof record-type failure mode (drift, python-
    # only type, C-only type); the covered twin (sadd) and the matching
    # REC_BATCH stay silent
    nc = by["NATIVE-CONTRACT"]
    assert {f.token for f in nc} == \
        {"zadd", "smembers:unroutable", "aof:frame:drift",
         "aof:chunk:missing-from-table", "aof:wmark:unknown-record-type"}
    assert [f.qualname for f in nc if f.token == "zadd"] == ["_plan_zadd"]
    assert [f.qualname for f in nc if f.token.endswith(":unroutable")] \
        == ["smembers_command"]
    # AWAIT-ATOMICITY: the PR 2 close-window and PR 12 quiesce-callback
    # race shapes; the post-fix re-reading forms and the pinned
    # deliberate snapshot stay silent
    aa = by["AWAIT-ATOMICITY"]
    assert {f.token for f in aa} == {"links", "pend"}
    assert {f.qualname.rsplit(".", 1)[-1] for f in aa} == \
        {"close_bad", "quiesce_bad"}
    # SLOT-EPOCH: the cached-epoch ownership flip; the re-reading and
    # pinned forms stay silent, and the general AWAIT-ATOMICITY rule
    # does not cover cluster/ (the specialization owns that dir)
    se = by["SLOT-EPOCH"]
    assert {f.token for f in se} == {"epoch"}
    assert [f.qualname for f in se] == ["flip_bad"]
    assert not any(f.path.startswith("cluster") for f in aa)
    # CUT-ORDERING: the PR 11 consistency-cut shape (export awaited
    # before the watermark capture), incl. the some-path branchy case;
    # the capture-first forms stay silent
    co = by["CUT-ORDERING"]
    assert {f.token for f in co} == {"_local_digest", "key_count"}
    assert {f.qualname.rsplit(".", 1)[-1] for f in co} == \
        {"send_delta_bad", "export_branchy_bad"}
    # LOCK-DISCIPLINE: await under a thread lock + blocking IO /
    # .result() under an asyncio lock; the snapshot-then-release and
    # run_in_executor forms stay silent
    ld = by["LOCK-DISCIPLINE"]
    assert {f.token for f in ld} == \
        {"self._crc_lock", "self._stream_lock:open",
         "self._stream_lock:.result()"}
    assert not any("fixed" in f.qualname for f in ld)
    # STAGE-AWAIT: an await and an `async for` inside an open stage; the
    # write-only form and a nested def's own await stay silent
    sa = by["STAGE-AWAIT"]
    assert {f.token for f in sa} == \
        {"self.node.stages.stage", "self._stage"}
    assert {f.qualname.rsplit(".", 1)[-1] for f in sa} == \
        {"reply_bad", "relay_bad"}


def test_findings_have_location_and_hint(corpus_findings):
    for f in corpus_findings:
        assert f.path and f.line > 0 and f.message
        assert f.hint, f"{f.rule} ships without a fix hint"
        assert f.key.startswith(f"{f.rule}:{f.path}:")
        assert f"{f.path}:{f.line}" in f.render()


def test_ignore_escape_hatch(tmp_path):
    bad = tmp_path / "parallel" / "x.py"
    bad.parent.mkdir()
    src = ("from multiprocessing import shared_memory\n"
           "def f(n):\n"
           "    a = shared_memory.SharedMemory(create=True, size=n)\n"
           "    b = shared_memory.SharedMemory(  # lint: ignore[SHM-LIFECYCLE]\n"
           "        create=True, size=n)\n"
           "    return a, b\n")
    bad.write_text(src)
    got = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert [f.token for f in got] == ["a"], got


# --------------------------------------------------------------- baseline

def test_baseline_growth_detection(corpus_findings):
    from constdb_tpu.analysis.core import baseline_payload
    base = baseline_payload(corpus_findings, notes={})
    # exact tree vs its own baseline: no growth, nothing stale
    growth, stale = compare_to_baseline(corpus_findings, base)
    assert growth == [] and stale == []
    # one more finding with a baselined key -> growth of exactly one
    extra = corpus_findings[0]
    growth, _ = compare_to_baseline(corpus_findings + [extra], base)
    assert len(growth) == 1 and growth[0].key == extra.key
    # removing a finding -> stale key reported, still no growth
    growth, stale = compare_to_baseline(corpus_findings[1:], base)
    assert growth == [] and stale == [corpus_findings[0].key]


def test_live_tree_clean_against_baseline():
    """THE gate: the package + README carry no findings beyond the
    committed baseline (constdb_tpu/analysis/baseline.json)."""
    findings = run_default_analysis() + check_readme_registry()
    growth, _stale = compare_to_baseline(findings, load_baseline())
    assert growth == [], "new lint findings:\n" + \
        "\n".join(f.render() for f in growth)


def test_baselined_keys_carry_notes():
    """Every baselined finding family has a tracking note — a baseline
    entry nobody can explain is just a muted alarm."""
    base = load_baseline()
    notes = base.get("notes", {})
    for key in base.get("findings", {}):
        assert any(key.startswith(p) for p in notes), \
            f"baselined key has no tracking note prefix: {key}"


def test_cli_baseline_mode_green(capsys):
    assert lint_main(["--baseline"]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_plain_mode_reports(capsys):
    rc = lint_main([CORPUS, "--root", CORPUS])
    out = capsys.readouterr().out
    assert rc == 1 and "finding(s)" in out


def test_cli_json_mode(capsys, corpus_findings):
    """--json: stable keys matching baseline.json, both modes."""
    import json
    rc = lint_main([CORPUS, "--root", CORPUS, "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1 and payload["version"] == 1
    assert len(payload["findings"]) == len(corpus_findings)
    # the counts map IS the baseline.json findings shape
    from constdb_tpu.analysis.core import baseline_payload
    assert payload["counts"] == \
        baseline_payload(corpus_findings, {})["findings"]
    for f in payload["findings"]:
        assert f["key"] == \
            f"{f['rule']}:{f['path']}:{f['qualname']}:{f['token']}"
    # baseline mode: growth/stale keys in the payload, clean -> rc 0
    rc = lint_main(["--baseline", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0 and payload["baseline"]["growth"] == []


# ----------------------------------------------------------- env registry

def test_registry_documented_in_readme():
    assert check_readme_registry() == []


def test_env_helpers_and_registry_discipline(monkeypatch):
    monkeypatch.setenv("CONSTDB_POOL_FLUSH_MB", "64")
    assert conf.env_int("CONSTDB_POOL_FLUSH_MB", 1536) == 64
    monkeypatch.delenv("CONSTDB_POOL_FLUSH_MB")
    assert conf.env_int("CONSTDB_POOL_FLUSH_MB", 1536) == 1536
    monkeypatch.setenv("CONSTDB_PIPELINE", "0")
    assert conf.env_flag("CONSTDB_PIPELINE", True) is False
    monkeypatch.setenv("CONSTDB_PIPELINE", "1")
    assert conf.env_flag("CONSTDB_PIPELINE", True) is True
    with pytest.raises(KeyError):
        conf.env_str("CONSTDB_NOT_A_REAL_KNOB")
