"""hvd-model protocol checker tests (horovod_tpu/analysis/model.py,
horovod_tpu/analysis/protocol.py, tools/hvd_model.py).

Covers: the no-forked-model contract (the live runtime demonstrably calls
the SAME pure transition functions the checker explores — functional
equivalence plus source-level call-site assertions), the shipped-protocol
sweep coming up clean for N in {2,3} with and without injected faults,
EXACT state/transition-count pins for every standard world (silent
search-space shrinkage fails CI), detection of every HVD201-HVD206 rule
on deliberately-broken protocol variants with minimal counterexample
traces, the three .world.json corpus fixtures (CLI exit code EXACTLY 1),
the shrink->continue executable spec, world-file parsing errors, and the
HOROVOD_MODEL_MAX_STATES / HOROVOD_MODEL_FAULTS knobs (typo path per
knob, validated at hvd.init)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import horovod_tpu as hvd
from horovod_tpu.analysis import model, protocol as proto
from horovod_tpu.analysis.model import Collective, World
from horovod_tpu.core import multihost as _mh
from horovod_tpu.core import negotiate as _neg
from horovod_tpu.core import resilience as _res
from horovod_tpu.core.state import HorovodError
from horovod_tpu.utils import env as _env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO, "tests", "lint_corpus")
CLI = os.path.join(REPO, "tools", "hvd_model.py")


@pytest.fixture(scope="module")
def nojax(tmp_path_factory):
    """Env overlay that makes ``import jax`` fail in subprocesses — every
    CLI invocation below runs through the namespace-stub path, pinning the
    acceptance criterion that hvd-model is jax-less (and keeping these
    subprocess tests fast: no jax import per spawn)."""
    blocker = tmp_path_factory.mktemp("nojax")
    (blocker / "jax.py").write_text(
        "raise ImportError('jax blocked: hvd-model must run jax-less')\n")
    path = str(blocker)
    if os.environ.get("PYTHONPATH"):
        path += os.pathsep + os.environ["PYTHONPATH"]
    return {"PYTHONPATH": path}


def _cli(*args: str, env_extra: dict | None = None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, CLI, *args], env=env,
                          capture_output=True, text=True, timeout=300)


# ---------------------------------------------------------------------------
# No forked model: the live runtime executes the checker's functions
# ---------------------------------------------------------------------------


class TestSharedTransitionFunctions:
    def test_negotiate_enum_values_come_from_protocol(self):
        assert _neg.CollectiveOp.ALLREDUCE.value == proto.OP_ALLREDUCE
        assert _neg.CollectiveOp.REDUCESCATTER.value == proto.OP_REDUCESCATTER
        assert {op.value for op in _neg.CollectiveOp} == set(proto.OP_NAMES)

    def test_validate_raises_protocols_exact_message(self):
        reqs = [
            _neg.Request(rank=0, name="t", op=_neg.CollectiveOp.ALLREDUCE,
                         dtype="f32", shape=(4,)),
            _neg.Request(rank=1, name="t", op=_neg.CollectiveOp.ALLREDUCE,
                         dtype="f64", shape=(4,)),
        ]
        verdict = proto.validate_requests(
            tuple(_neg._to_proto(r) for r in reqs), 2)
        assert verdict.error is not None
        with pytest.raises(HorovodError) as e:
            _neg.validate(reqs, 2)
        assert str(e.value) == verdict.error
        assert "Mismatched data types" in verdict.error

    def test_validate_success_matches_protocol_verdict(self):
        reqs = [
            _neg.Request(rank=r, name="g", op=_neg.CollectiveOp.ALLGATHER,
                         dtype="f32", shape=(2 + r, 3))
            for r in range(3)
        ]
        resp = _neg.validate(reqs, 3)
        verdict = proto.validate_requests(
            tuple(_neg._to_proto(r) for r in reqs), 3)
        assert verdict.error is None
        assert resp.tensor_sizes == verdict.tensor_sizes == (2, 3, 4)
        assert resp.op.value == verdict.op

    def test_negotiator_keys_are_protocol_keys(self):
        n = _mh.Negotiator(generation=7)
        assert n._key(3, 2) == proto.neg_key(7, 3, 2) \
            == "hvd/neg/g7/s3/p2"
        assert n._verdict_key(4) == proto.verdict_key(7, 4) \
            == "hvd/resp/g7/s4"
        assert proto.key_generation(n._key(3, 2)) == 7
        assert proto.key_generation("not/a/gen/key") is None

    def test_resilience_classifier_is_protocol_classifier(self):
        for msg in ("DEADLINE_EXCEEDED: GetKeyValue() timed out",
                    "UNAVAILABLE: connection timed out",
                    "CANCELLED: coordination service has stopped",
                    "something novel"):
            assert _res.classify_kv_error(Exception(msg)) \
                == proto.classify_kv_message(msg)

    def test_fault_grammar_is_shared_not_forked(self):
        assert _res.parse_fault_spec is proto.parse_fault_spec
        assert _res.Fault is proto.Fault

    def test_injector_matchers_delegate_to_protocol(self):
        faults = proto.parse_fault_spec("kv_timeout@seq=2,times=3")
        inj = _res.FaultInjector(faults)
        for s in range(8):
            assert (inj.kv_fault_due(s) is not None) \
                == (proto.kv_fault_covering(faults, s) is not None)
        cf = proto.parse_fault_spec("crash@rank=1,step=5")
        inj2 = _res.FaultInjector(cf)
        assert inj2.crash_due(5, ranks=(1,)) is \
            proto.crash_fault_matching(cf, 5, (1,))
        assert inj2.crash_due(5, ranks=(0,)) is None

    def test_agree_epochs_matches_checkpoint_semantics(self):
        # Newest common epoch, never the min-of-newest.
        assert proto.agree_epochs([{0, 1, 3}, {0, 3}, {1, 3}]) == (3, 3)
        assert proto.agree_epochs([{0, 1}, {2}]) == (-1, 2)
        assert proto.agree_epochs([set(), {4}]) == (-1, 4)
        assert proto.agree_epochs([]) == (-1, -1)
        assert proto.agree_epochs([set(), set()]) == (-1, -1)

    def test_retry_decision_matches_kv_call_branching(self):
        assert proto.retry_decision("pending", "get", 0, 3, "x") == "raise"
        assert proto.retry_decision("fatal", "get", 0, 3, "x") == "raise"
        assert proto.retry_decision("transient", "get", 0, 3, "x") == "retry"
        assert proto.retry_decision("transient", "get", 3, 3, "x") \
            == "exhausted"
        assert proto.retry_decision(
            "fatal", "set", 1, 3, "ALREADY_EXISTS: key") == "duplicate_ok"
        # First-attempt duplicate is a genuine collision: surfaced.
        assert proto.retry_decision(
            "fatal", "set", 0, 3, "ALREADY_EXISTS: key") == "raise"

    def test_live_modules_call_protocol_at_the_refactored_sites(self):
        # "Demonstrably call the same pure transition functions": the
        # acceptance criterion, pinned at source level so a rewrite that
        # re-forks the logic fails loudly.
        expectations = {
            "horovod_tpu/core/multihost.py": [
                "_proto.coordinate(", "_proto.replay_fingerprint(",
                "_proto.neg_key(", "_proto.verdict_key(",
                "_proto.sched_key(", "_proto.first_divergence(",
            ],
            "horovod_tpu/core/resilience.py": [
                "_proto.classify_kv_message(", "_proto.retry_decision(",
                "_proto.kv_fault_covering(", "_proto.crash_fault_matching(",
                "_proto.torn_write_index(", "_proto.judge_dead(",
                "_proto.liveness_probe_order(", "_proto.hb_key(",
            ],
            "horovod_tpu/core/negotiate.py": [
                "_proto.validate_requests(",
            ],
            "horovod_tpu/training/checkpoint.py": [
                "_proto.agree_epochs(",
            ],
            # Serving resilience (ISSUE 19): the live journal loader,
            # the hvd-lint artifact verifier, and the model checker all
            # run the SAME committed-token fold; the engine/scheduler
            # judge deadlines, admission feasibility, stalls, and
            # accept-rate collapse through the protocol module too.
            "horovod_tpu/serving/resilience.py": [
                "_proto.journal_committed(", "_proto.judge_dead(",
            ],
            "horovod_tpu/analysis/schedule.py": [
                "_proto.journal_committed(",
            ],
            "horovod_tpu/serving/engine.py": [
                "_proto.deadline_expired(",
                "_proto.accept_rate_collapsed(",
            ],
            "horovod_tpu/serving/scheduler.py": [
                "_proto.deadline_expired(", "_proto.admission_feasible(",
            ],
        }
        for rel, needles in expectations.items():
            with open(os.path.join(REPO, rel)) as f:
                src = f.read()
            for needle in needles:
                assert needle in src, f"{rel} no longer calls {needle}"


# ---------------------------------------------------------------------------
# The shipped protocol sweeps clean — with exact exhaustiveness pins
# ---------------------------------------------------------------------------

# (label suffix, nprocs) -> (states, transitions) with the default POR.
# These are EXACT: fewer states means the explorer silently stopped
# covering interleavings (a broken guard, an over-eager reduction); more
# means the worlds or transition system changed — re-derive deliberately
# with: python tools/hvd_model.py (counts print per world).
EXPECTED_COUNTS = {
    ("eager", 2): (11, 13),
    ("memberless", 2): (11, 13),
    ("allgather", 2): (9, 10),
    ("checkpoint", 2): (17, 24),
    ("shrink", 2): (9, 9),
    ("regrow", 2): (11, 13),
    ("journal", 2): (6, 5),
    ("eager", 3): (22, 34),
    ("memberless", 3): (22, 34),
    ("allgather", 3): (17, 25),
    ("checkpoint", 3): (37, 71),
    ("shrink", 3): (21, 30),
    ("regrow", 3): (25, 40),
    ("journal", 3): (8, 8),
}


def _world_kind(label: str) -> str:
    return label.split(":")[1].split("-")[0]


class TestShippedProtocolSweep:
    @pytest.mark.parametrize("n", [2, 3])
    def test_fault_free_sweep_clean(self, n):
        for world in model.standard_worlds(n):
            result = model.check_world(world)
            assert result.ok, "\n".join(str(f) for f in result.findings)

    @pytest.mark.parametrize("n", [2, 3])
    def test_exhaustiveness_pinned(self, n):
        for world in model.standard_worlds(n):
            result = model.check_world(world)
            want = EXPECTED_COUNTS[(_world_kind(world.label), n)]
            assert (result.states, result.transitions) == want, (
                f"{world.label}: explored {result.states} states / "
                f"{result.transitions} transitions, pinned {want} — the "
                f"search space silently changed")

    @pytest.mark.parametrize("n", [2, 3])
    def test_fault_sweeps_clean(self, n):
        for spec in model.default_fault_specs(n):
            faults = proto.parse_fault_spec(spec)
            for world in model.standard_worlds(n, faults):
                result = model.check_world(world)
                assert result.ok, (
                    spec + "\n" + "\n".join(str(f) for f in result.findings))

    @pytest.mark.parametrize("n", [2, 3])
    def test_por_off_reaches_same_verdict(self, n):
        # The reduction must only collapse commuting orders, never hide a
        # violation: the unreduced graph (strictly more states) agrees.
        for world in model.standard_worlds(n):
            reduced = model.check_world(world)
            full = model.check_world(world, por=False)
            assert full.ok == reduced.ok
            assert full.states >= reduced.states

    def test_unbounded_kv_burst_fails_cleanly_not_wedged(self):
        # times > retries: exhaustion is the DESIGNED outcome — processes
        # fail with a bounded-retry error and peers get liveness verdicts;
        # no deadlock, and no HVD203 (the burst was not bounded).
        faults = proto.parse_fault_spec("kv_timeout@seq=0,times=99")
        world = model.standard_worlds(2, faults)[0]
        result = model.check_world(world)
        assert result.ok, "\n".join(str(f) for f in result.findings)


# ---------------------------------------------------------------------------
# Every invariant is detectable (broken-variant worlds)
# ---------------------------------------------------------------------------


def _ar(name, members):
    return Collective(name, proto.OP_ALLREDUCE, tuple(members))


class TestInvariantDetection:
    def test_hvd201_split_brain(self):
        g = Collective("gather_x", proto.OP_ALLGATHER, (0, 1),
                       shapes=((4, 2), (6, 2)))
        world = World("w", 2, tuple((("negotiate", g),) for _ in range(2)),
                      variant="premature_verdict")
        rules = {f.rule for f in model.check_world(world).findings}
        assert rules == {"HVD201"}

    def test_hvd202_deadlock_extra_collective(self):
        world = World("w", 2, (
            (("negotiate", _ar("a", (0, 1))),),
            (("negotiate", _ar("a", (0, 1))),
             ("negotiate", _ar("b", (0, 1)))),
        ))
        findings = model.check_world(world).findings
        assert [f.rule for f in findings] == ["HVD202"]
        assert "Counterexample" in findings[0].message
        assert " -> " in findings[0].message

    def test_hvd203_faulted_deadlock(self):
        # The same divergence under injected faults reports as a
        # progress-under-faults violation.
        world = World("w", 2, (
            (("negotiate", _ar("a", (0, 1))),),
            (("negotiate", _ar("a", (0, 1))),
             ("negotiate", _ar("b", (0, 1)))),
        ), faults=proto.parse_fault_spec("kv_timeout@seq=1"))
        rules = {f.rule for f in model.check_world(world).findings}
        assert rules == {"HVD203"}

    def test_hvd204_torn_write_elected(self):
        post = _ar("post", (0, 1))
        world = World(
            "w", 2,
            tuple((("save", 0), ("save", 1), ("restore", 0),
                   ("negotiate", post)) for _ in range(2)),
            variant="elect_unverified",
            faults=proto.parse_fault_spec("torn_write@epoch=1"))
        findings = model.check_world(world).findings
        assert {f.rule for f in findings} == {"HVD204"}
        assert "TORN" in findings[0].message

    def test_hvd204_replay_torn_tail(self):
        # The serve-journal invariant: a replay that CONSUMES the torn
        # record a crash left (instead of dropping it and recomputing)
        # commits tokens no verified record vouches for — crash-unsafe
        # restore, same rule as electing a torn checkpoint.
        world = World(
            "w", 2,
            ((("jadmit", 0), ("jemit", 0), ("jemit", 0), ("crash",)),
             (("jreplay", 0),)),
            variant="replay_torn_tail",
            faults=proto.parse_fault_spec("torn_write@epoch=1"))
        findings = model.check_world(world).findings
        assert {f.rule for f in findings} == {"HVD204"}
        assert "TORN" in findings[0].message

    def test_hvd205_stale_generation_read(self):
        world = World(
            "w", 2,
            tuple((("negotiate", _ar("a", (0, 1))), ("restore", 0),
                   ("negotiate", _ar("b", (0, 1)))) for _ in range(2)),
            variant="stale_generation_read")
        rules = {f.rule for f in model.check_world(world).findings}
        assert "HVD205" in rules
        assert "HVD201" in rules  # the stale verdict is also a split brain

    def test_hvd206_memberless_skips_negotiation(self):
        sub = _ar("subset_sum", (0, 1))
        world = World("w", 3,
                      tuple((("negotiate", sub),) for _ in range(3)),
                      variant="skip_memberless")
        findings = model.check_world(world).findings
        assert [f.rule for f in findings] == ["HVD206"]

    def test_counterexample_traces_are_minimal(self):
        # BFS re-sweep: the deadlock above needs exactly 5 steps (submit,
        # submit, collect, read, extra submit) — no longer trace reported.
        world = World("w", 2, (
            (("negotiate", _ar("a", (0, 1))),),
            (("negotiate", _ar("a", (0, 1))),
             ("negotiate", _ar("b", (0, 1)))),
        ))
        msg = model.check_world(world).findings[0].message
        assert "Counterexample (5 steps)" in msg


# ---------------------------------------------------------------------------
# Shrink -> continue: the executable spec for the elastic PR (ROADMAP #3)
# ---------------------------------------------------------------------------


class TestShrinkSpec:
    def test_plan_is_deterministic_and_agreed(self):
        plan0 = proto.plan_shrink((0, 1, 2, 3), dead=(2,), generation=5)
        plan1 = proto.plan_shrink((0, 1, 2, 3), dead=(2,), generation=5)
        assert plan0 == plan1
        assert plan0.survivors == (0, 1, 3)
        assert plan0.coordinator == 0
        assert plan0.generation == 6

    def test_dead_coordinator_reelects_lowest_survivor(self):
        plan = proto.plan_shrink((0, 1, 2), dead=(0,), generation=1)
        assert plan.coordinator == 1
        assert plan.survivors == (1, 2)

    def test_no_survivors_raises(self):
        with pytest.raises(ValueError, match="no survivors"):
            proto.plan_shrink((0, 1), dead=(0, 1), generation=1)

    @pytest.mark.parametrize("n", [2, 3])
    def test_shrink_world_sweeps_clean_and_agrees(self, n):
        world = [w for w in model.standard_worlds(n)
                 if "shrink" in w.label][0]
        result = model.check_world(world)
        assert result.ok, "\n".join(str(f) for f in result.findings)
        # Post-shrink negotiation really happened in the bumped
        # generation: the spec the elastic PR lands against.
        assert result.terminals == 1


# ---------------------------------------------------------------------------
# World files + CLI
# ---------------------------------------------------------------------------


class TestWorldFilesAndCli:
    @pytest.mark.parametrize("fixture,rule", [
        ("bad_protocol_deadlock.world.json", "HVD202"),
        ("bad_split_brain.world.json", "HVD201"),
        ("bad_stale_generation.world.json", "HVD205"),
    ])
    def test_corpus_fixture_exits_exactly_one(self, fixture, rule, nojax):
        # Exit EXACTLY 1, and jax-less: a checker crash must not pass as
        # 'detected' (the PR 7 corpus convention), and the CLI must run
        # on a bare interpreter (the CI lint job).
        proc = _cli(os.path.join(CORPUS, fixture), env_extra=nojax)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert f"{fixture}:1: {rule}" in proc.stdout

    def test_sweep_cli_clean_exit_zero_jaxless(self, nojax):
        proc = _cli(env_extra=nojax)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "protocol sweep" in proc.stdout
        assert "clean" in proc.stdout

    def test_list_rules(self, nojax):
        proc = _cli("--list-rules", env_extra=nojax)
        assert proc.returncode == 0
        for rule in ("HVD201", "HVD202", "HVD203", "HVD204", "HVD205",
                     "HVD206"):
            assert rule in proc.stdout
        assert "HVD101" not in proc.stdout  # hvd-lint owns those

    def test_bad_faults_spec_exits_two(self, nojax):
        proc = _cli("--faults", "kv_timeout@sq=3", env_extra=nojax)
        assert proc.returncode == 2
        assert "sq" in proc.stderr

    def test_max_states_overflow_exits_two(self, nojax):
        proc = _cli("--max-states", "3", env_extra=nojax)
        assert proc.returncode == 2
        assert "max_states" in proc.stderr

    def test_unknown_target_rejected(self, nojax):
        proc = _cli(os.path.join(CORPUS, "bad_wire_dtype.hlo"),
                    env_extra=nojax)
        assert proc.returncode == 2
        assert "hvd-lint owns" in proc.stderr + proc.stdout

    def test_world_from_json_errors(self):
        with pytest.raises(ValueError, match="unknown step kind"):
            model.world_from_json(json.dumps(
                {"scripts": [[{"step": "negotiatee", "name": "x",
                               "op": "allreduce", "members": [0]}]]}))
        with pytest.raises(ValueError, match="nprocs=3"):
            model.world_from_json(json.dumps(
                {"nprocs": 3, "scripts": [[]]}), path="w")
        # Schema-shaped crashes (wrong types, unknown ops, missing keys)
        # surface as ValueError naming the file, never TypeError/KeyError.
        for bad in ({"scripts": "oops"},
                    {"scripts": ["oops"]},
                    {"scripts": [[{"step": "negotiate", "name": "x",
                                   "op": "allredcue", "members": [0]}]]},
                    {"scripts": [[{"step": "save"}]]},
                    {"scripts": [[{"no": "step"}]]},
                    ["not", "an", "object"]):
            with pytest.raises(ValueError, match="w:"):
                model.world_from_json(json.dumps(bad), path="w")

    def test_malformed_world_file_exits_two_not_one(self, tmp_path, nojax):
        # A checker/schema crash must report exit 2 (internal/usage
        # error), never 1 — the corpus gate's exit-EXACTLY-1 contract.
        bad = tmp_path / "broken.world.json"
        bad.write_text(json.dumps({"scripts": "oops"}))
        proc = _cli(str(bad), env_extra=nojax)
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "scripts" in proc.stderr

    def test_world_from_json_round_trip(self):
        text = json.dumps({
            "label": "w", "nprocs": 2, "variant": None, "cache": False,
            "faults": "kv_timeout@seq=1,times=2",
            "scripts": [
                [{"step": "negotiate", "name": "a", "op": "broadcast",
                  "members": [0, 1], "root": 1},
                 {"step": "restore"}],
                [{"step": "negotiate", "name": "a", "op": "broadcast",
                  "members": [0, 1], "root": 1},
                 {"step": "restore"}],
            ]})
        world = model.world_from_json(text)
        assert world.nprocs == 2 and not world.cache_enabled
        assert world.faults[0].kind == "kv_timeout"
        step = world.scripts[0][0]
        assert step[0] == "negotiate"
        assert step[1].op == proto.OP_BROADCAST and step[1].root == 1
        assert world.scripts[0][1] == ("restore", 0)
        result = model.check_world(world)
        assert result.ok, "\n".join(str(f) for f in result.findings)


# ---------------------------------------------------------------------------
# Env knobs
# ---------------------------------------------------------------------------


class TestEnvKnobs:
    def test_defaults(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_MODEL_MAX_STATES", raising=False)
        monkeypatch.delenv("HOROVOD_MODEL_FAULTS", raising=False)
        assert _env.model_max_states() == model.DEFAULT_MAX_STATES
        assert _env.model_faults() is None

    def test_valid_values(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_MODEL_MAX_STATES", "5000")
        assert _env.model_max_states() == 5000
        monkeypatch.setenv("HOROVOD_MODEL_FAULTS", "crash@rank=0,step=1")
        assert _env.model_faults() == "crash@rank=0,step=1"

    @pytest.mark.parametrize("bad", ["many", "2.5", "0", "-3"])
    def test_max_states_typo_raises(self, monkeypatch, bad):
        monkeypatch.setenv("HOROVOD_MODEL_MAX_STATES", bad)
        with pytest.raises(ValueError, match="HOROVOD_MODEL_MAX_STATES"):
            _env.model_max_states()

    @pytest.mark.parametrize("bad", ["kv_timeout", "crash@rnk=1,step=2",
                                     "meteor@strike=1"])
    def test_model_faults_typo_raises(self, monkeypatch, bad):
        monkeypatch.setenv("HOROVOD_MODEL_FAULTS", bad)
        with pytest.raises(ValueError):
            _env.model_faults()

    def test_registered(self):
        assert "HOROVOD_MODEL_MAX_STATES" in _env.KNOWN_ENV_VARS
        assert "HOROVOD_MODEL_FAULTS" in _env.KNOWN_ENV_VARS

    @pytest.mark.parametrize("knob,bad", [
        ("HOROVOD_MODEL_MAX_STATES", "bogus"),
        ("HOROVOD_MODEL_FAULTS", "bogus@spec=x"),
    ])
    def test_typo_raises_at_init(self, monkeypatch, knob, bad):
        hvd.shutdown()
        monkeypatch.setenv(knob, bad)
        with pytest.raises(ValueError):
            hvd.init()
        monkeypatch.delenv(knob)
        hvd.shutdown()
        hvd.init()  # recovers cleanly once the typo is fixed
        hvd.shutdown()

    def test_model_limit_raises_in_process(self):
        world = model.standard_worlds(2)[0]
        with pytest.raises(model.ModelLimit, match="max_states"):
            model.check_world(world, max_states=3)
