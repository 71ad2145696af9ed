"""Pod-level coordinated recovery (ddl_tpu/coord.py + PodSupervisor).

Unit tier: the rendezvous primitives (barrier, stale-peer ageout,
split-brain-free restart-epoch proposal under a real race, rank-0
resume-epoch agreement) and the PodSupervisor protocol driven by
scripted fake children over one tmpdir "NAS".

End-to-end tier: a 3-process pod sim — real tiny-LM trainer children
under real pod supervisors sharing one tmpdir — where an injected
``stall@step`` hang on host 1 makes all three hosts exit and relaunch
in the same restart epoch, restore the same (rank-0-agreed) snapshot,
and reach the same final step and identical final weights, with the
consumed-batch audit proving the resumed stream replayed no batch and
skipped none (the data cursor).
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from ddl_tpu.coord import (
    BarrierTimeout,
    Rendezvous,
    agreed_resume_epoch,
    from_env,
)
from ddl_tpu.supervisor import EXIT_PREEMPTED, EXIT_REJOIN, PodSupervisor
from ddl_tpu.utils.backoff import Backoff

CHILD = Path(__file__).parent / "pod_sim_child.py"


def _rv(root, host, n, **kw):
    kw.setdefault("timeout_s", 10.0)
    kw.setdefault("poll_s", 0.005)
    return Rendezvous(root, host, n, **kw)


# ---------------------------------------------------------------------------
# rendezvous primitives
# ---------------------------------------------------------------------------


def test_barrier_completes_when_all_arrive(tmp_path):
    done = []

    def host(i):
        rv = _rv(tmp_path, i, 3)
        rv.barrier("go")
        done.append(i)

    threads = [threading.Thread(target=host, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert sorted(done) == [0, 1, 2]


def test_barrier_times_out_when_a_peer_never_arrives(tmp_path):
    rv = _rv(tmp_path, 0, 2, timeout_s=0.2)
    with pytest.raises(BarrierTimeout, match="1/2 hosts"):
        rv.barrier("lonely")


def test_stale_peer_ageout_only_for_running_hosts(tmp_path):
    a, b, c = _rv(tmp_path, 0, 3), _rv(tmp_path, 1, 3), _rv(tmp_path, 2, 3)
    b.publish_heartbeat("running", 0)
    c.publish_heartbeat("done", 0)
    time.sleep(0.15)
    # b aged out while "running"; c is parked "done" and never stale
    assert a.stale_peers(0.1) == [1]
    assert a.stale_peers(10.0) == []
    b.publish_heartbeat("running", 0)  # a fresh beat clears it
    assert a.stale_peers(0.1) == []


def test_membership_scopes_barriers_peers_and_agreement(tmp_path):
    """Elastic membership: barriers complete over the LIVE member set,
    evicted hosts' heartbeats go invisible, and the agreement leader is
    the lowest surviving id."""
    a = _rv(tmp_path, 0, 3)
    c = _rv(tmp_path, 2, 3)
    # host 1 beat once, then was evicted
    _rv(tmp_path, 1, 3).publish_heartbeat("running", 0)
    a.adopt_membership([0, 2])
    c.adopt_membership([0, 2])
    assert a.world == 2 and a.leader == 0 and a.members == (0, 2)
    time.sleep(0.15)
    assert a.stale_peers(0.1) == []  # the casualty is not re-judged
    # a 2-member barrier completes without host 1
    done = []

    def arrive(rv):
        rv.barrier("shrunk")
        done.append(rv.host)

    t = threading.Thread(target=arrive, args=(c,))
    t.start()
    arrive(a)
    t.join(timeout=10)
    assert sorted(done) == [0, 2]
    assert sorted(a.barrier_arrivals("shrunk")) == [0, 2]
    # eviction is loud: an excluded host cannot adopt the membership
    with pytest.raises(ValueError, match="evicted"):
        _rv(tmp_path, 1, 3).adopt_membership([0, 2])


def test_restart_epoch_proposal_is_split_brain_free(tmp_path):
    """N hosts racing to propose the same restart epoch converge on ONE
    record: one proposer, one cumulative crash count, one agreed
    delay."""
    records = {}

    def propose(i):
        rv = _rv(tmp_path, i, 4)
        records[i] = rv.propose_restart(
            0, reason=f"crash-h{i}", crash=True, preempt=False,
            delay_fn=lambda c: 1.0 + i,  # would differ per host if raced
        )

    threads = [threading.Thread(target=propose, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert len({r["proposer"] for r in records.values()}) == 1
    assert len({r["delay"] for r in records.values()}) == 1
    for r in records.values():
        assert r["epoch"] == 1
        assert r["crashes"] == 1  # one restart event, counted once
    # the ledger rolls counts forward epoch over epoch
    rv = _rv(tmp_path, 0, 4)
    rec2 = rv.propose_restart(1, "crash", crash=True, preempt=False)
    assert rec2["epoch"] == 2 and rec2["crashes"] == 2


def test_rank0_resume_agreement_overrides_divergent_views(tmp_path, monkeypatch):
    """Torn-NAS shape: hosts compute different latest_valid_epoch; every
    host must restore rank 0's answer."""
    values = {0: 12, 1: 4}  # host 1's local view lags (torn write)
    got = {}

    def host(i):
        rv = _rv(tmp_path, i, 2)
        got[i] = rv.agree("resume-job-e1", lambda: values[i])

    t1 = threading.Thread(target=host, args=(1,))
    t1.start()
    time.sleep(0.05)  # host 1 is already waiting when rank 0 decides
    host(0)
    t1.join(timeout=10)
    assert got == {0: 12, 1: 12}

    # the env-driven wrapper used by checkpoint.resolve_resume
    monkeypatch.setenv("DDL_COORD_DIR", str(tmp_path))
    monkeypatch.setenv("DDL_COORD_HOSTS", "2")
    monkeypatch.setenv("DDL_COORD_HOST", "0")
    monkeypatch.setenv("DDL_RESTART_EPOCH", "2")
    assert from_env().host == 0
    assert agreed_resume_epoch("job", lambda: 7) == 7
    monkeypatch.setenv("DDL_COORD_HOST", "1")
    assert agreed_resume_epoch("job", lambda: 3) == 7  # rank 0's answer
    monkeypatch.delenv("DDL_COORD_DIR")
    assert from_env() is None
    assert agreed_resume_epoch("job", lambda: 5) == 5  # non-pod fallback


def test_abort_is_pod_wide_and_first_writer_wins(tmp_path):
    a, b = _rv(tmp_path, 0, 2), _rv(tmp_path, 1, 2)
    rec = a.abort("crash budget exhausted", 9)
    assert b.aborted()["rc"] == 9
    # a later abort keeps the original story
    assert b.abort("something else", 3)["reason"] == "crash budget exhausted"
    assert rec["host"] == 0


# ---------------------------------------------------------------------------
# PodSupervisor protocol (scripted fake children, threads as hosts)
# ---------------------------------------------------------------------------


class FakeChild:
    """Scripted child: exits ``rc`` after ``delay`` seconds, or hangs
    forever (rc=None) until terminated."""

    def __init__(self, rc=None, delay=0.05):
        self.rc = rc
        self.delay = delay
        self.t0 = time.monotonic()
        self.killed = False

    def poll(self):
        if self.killed:
            return -15
        if self.rc is None:
            return None
        return self.rc if time.monotonic() - self.t0 >= self.delay else None

    def terminate(self):
        self.killed = True

    kill = terminate

    def wait(self, timeout=None):
        return self.poll()


def _run_pod(tmp_path, scripts, n_hosts=None, events=None, **sup_kwargs):
    """Run one PodSupervisor per host in threads; ``scripts[i]`` is the
    list of children host i spawns, in order.  Returns {host: exit}."""
    n_hosts = n_hosts if n_hosts is not None else len(scripts)
    sup_kwargs.setdefault("backoff", Backoff(base=0.01, jitter=0.0))
    results = {}
    sups = {}

    def host(i):
        rv = _rv(tmp_path, i, n_hosts)
        it = iter(scripts[i])
        sup = PodSupervisor(
            lambda epoch, idx: next(it), rv,
            poll_s=0.005, heartbeat_s=0.02, stale_after_s=30.0,
            log=lambda m: None,
            events=(events or {}).get(i),
            **sup_kwargs,
        )
        sups[i] = sup
        results[i] = sup.run()

    threads = [
        threading.Thread(target=host, args=(i,)) for i in range(len(scripts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), "pod deadlocked"
    return results


def test_pod_completes_when_all_children_succeed(tmp_path):
    results = _run_pod(tmp_path, [[FakeChild(rc=0)], [FakeChild(rc=0)]])
    assert results == {0: 0, 1: 0}
    assert _rv(tmp_path, 0, 2).current_epoch() == 0  # no restart proposed


def test_one_crash_restarts_every_host_in_the_same_epoch(tmp_path):
    """Host 1 crashes; host 0's healthy child (hanging mid-'collective')
    is killed and both hosts relaunch together in restart epoch 1."""
    h0 = [FakeChild(rc=None), FakeChild(rc=0)]
    results = _run_pod(tmp_path, [h0, [FakeChild(rc=1), FakeChild(rc=0)]])
    assert results == {0: 0, 1: 0}
    assert h0[0].killed  # the healthy child was killed, not abandoned
    rv = _rv(tmp_path, 0, 2)
    assert rv.current_epoch() == 1
    rec = rv.epoch_record(1)
    assert rec["crashes"] == 1 and rec["reason"].endswith("crash")


def test_completed_host_rejoins_a_restart_proposed_while_it_waits(tmp_path):
    """Host 0 finishes its run; host 1 then crashes.  Host 0 must leave
    the done barrier and retrain — the resumed collective needs every
    host."""
    h0 = [FakeChild(rc=0, delay=0.01), FakeChild(rc=0)]
    h1 = [FakeChild(rc=1, delay=0.3), FakeChild(rc=0)]
    results = _run_pod(tmp_path, [h0, h1])
    assert results == {0: 0, 1: 0}
    assert _rv(tmp_path, 0, 2).current_epoch() == 1


def test_resumable_exits_do_not_consume_the_crash_budget(tmp_path):
    h0 = [FakeChild(rc=EXIT_PREEMPTED, delay=0.01), FakeChild(rc=0)]
    h1 = [FakeChild(rc=None), FakeChild(rc=0)]
    results = _run_pod(tmp_path, [h0, h1], max_restarts=0)
    assert results == {0: 0, 1: 0}  # survives despite a zero crash budget
    rec = _rv(tmp_path, 0, 2).epoch_record(1)
    assert rec["crashes"] == 0 and rec["preemptions"] == 1
    assert rec["delay"] == 0.0  # preemptions relaunch without backoff


def test_crash_budget_exhaustion_aborts_the_whole_pod(tmp_path):
    h0 = [FakeChild(rc=None), FakeChild(rc=None)]
    h1 = [FakeChild(rc=7, delay=0.01), FakeChild(rc=7, delay=0.01)]
    results = _run_pod(tmp_path, [h0, h1], max_restarts=1)
    # both hosts exit with the crashing host's code, not just the crasher
    assert results == {0: 7, 1: 7}
    ab = _rv(tmp_path, 0, 2).aborted()
    assert ab is not None and "crash budget" in ab["reason"]


def test_stale_peer_triggers_escalation_not_eternal_hang(tmp_path):
    """Host 1's supervisor dies silently (no heartbeat, child hangs).
    Host 0 must detect the aged-out heartbeat, attempt a coordinated
    restart, and — when the dead peer never joins the barrier — abort
    rather than hang forever."""
    rv1 = _rv(tmp_path, 1, 2)
    rv1.arrive("start")  # host 1 made the start barrier...
    rv1.publish_heartbeat("running", 0)  # ...beat once, then died

    rv0 = _rv(tmp_path, 0, 2, timeout_s=0.5)
    child = FakeChild(rc=None)
    sup = PodSupervisor(
        lambda epoch, idx: child, rv0,
        poll_s=0.005, heartbeat_s=0.02, stale_after_s=0.1,
        backoff=Backoff(base=0.01, jitter=0.0), log=lambda m: None,
    )
    rc = sup.run()
    assert rc != 0
    assert child.killed
    ab = rv0.aborted()
    assert ab is not None and "join" in ab["reason"]


def test_pod_supervisor_emits_coordination_events(tmp_path):
    from ddl_tpu.obs import EventWriter, read_events

    w0 = EventWriter(tmp_path / "logs", "podjob", host=0)
    results = _run_pod(
        tmp_path / "nas",
        [[FakeChild(rc=None), FakeChild(rc=0)],
         [FakeChild(rc=1, delay=0.01), FakeChild(rc=0)]],
        events={0: w0},
    )
    assert results == {0: 0, 1: 0}
    w0.close()
    events = read_events(w0.path)
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "supervisor_start"
    assert "coord_barrier" in kinds and "pod_restart" in kinds
    restart = next(e for e in events if e["kind"] == "pod_restart")
    # either host may win the proposal race; the classification must
    # still be the crash (reason "crash" from the crasher itself or
    # "peer_crash" from the bystander that saw its intent)
    assert restart["epoch"] == 1 and restart["reason"].endswith("crash")
    assert kinds[-1] == "supervisor_done"


# ---------------------------------------------------------------------------
# elastic mode: continue on N-1 (scripted fake children)
# ---------------------------------------------------------------------------


def test_elastic_stale_peer_evicted_after_grace_and_pod_continues(tmp_path):
    """Host 1's supervisor dies permanently right after the start
    barrier.  The elastic survivors hold the eviction grace, then agree
    restart epoch 1 with membership [0, 2] / world 2 — and finish as a
    2-host pod instead of aborting."""
    rv1 = _rv(tmp_path, 1, 3)
    rv1.arrive("start")
    rv1.publish_heartbeat("running", 0)  # beat once, then silence

    scripts = {0: [FakeChild(rc=None), FakeChild(rc=0)],
               2: [FakeChild(rc=None), FakeChild(rc=0)]}
    results = {}

    def host(i):
        rv = _rv(tmp_path, i, 3)
        it = iter(scripts[i])
        sup = PodSupervisor(
            lambda epoch, idx: next(it), rv,
            poll_s=0.005, heartbeat_s=0.02,
            stale_after_s=0.15, elastic=True, elastic_grace_s=0.2,
            backoff=Backoff(base=0.01, jitter=0.0), log=lambda m: None,
        )
        results[i] = sup.run()

    threads = [threading.Thread(target=host, args=(i,)) for i in (0, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), "elastic pod deadlocked"
    assert results == {0: 0, 2: 0}
    # the epoch-0 children (hung in the dead host's collective) were
    # killed, not abandoned
    assert scripts[0][0].killed and scripts[2][0].killed
    rv = _rv(tmp_path, 0, 3)
    assert rv.aborted() is None
    assert rv.current_epoch() == 1
    rec = rv.epoch_record(1)
    assert rec["reason"] == "peer_lost"
    assert rec["hosts"] == [0, 2] and rec["world"] == 2
    # an eviction is a preemption-class event, never a crash
    assert rec["crashes"] == 0 and rec["preemptions"] == 1


def test_elastic_join_barrier_timeout_scales_down_to_arrivals(tmp_path):
    """The second eviction route: a peer whose child crashed and whose
    supervisor then died never reaches the join barrier.  The arrived
    host proposes the NEXT epoch over the arrivals and continues
    alone."""
    rv1 = _rv(tmp_path, 1, 2)
    rv1.arrive("start")
    rv1.publish_heartbeat("running", 0)
    rv1.publish_intent("crash", 1, 0)  # child died; supervisor died too

    child0, child1 = FakeChild(rc=None), FakeChild(rc=0)
    it = iter([child0, child1])
    rv0 = _rv(tmp_path, 0, 2, timeout_s=0.4)
    sup = PodSupervisor(
        lambda epoch, idx: next(it), rv0,
        poll_s=0.005, heartbeat_s=0.02, stale_after_s=30.0,
        elastic=True,
        backoff=Backoff(base=0.01, jitter=0.0), log=lambda m: None,
    )
    assert sup.run() == 0
    assert child0.killed
    assert rv0.aborted() is None
    # epoch 1 = the crash restart (full membership, budget consumed);
    # epoch 2 = the join-timeout eviction (membership [0])
    assert rv0.current_epoch() == 2
    rec1, rec2 = rv0.epoch_record(1), rv0.epoch_record(2)
    assert rec1["crashes"] == 1
    assert rec2["reason"] == "peer_lost"
    assert rec2["hosts"] == [0] and rec2["world"] == 1
    assert rec2["crashes"] == 1  # rolled forward, not re-counted


def test_evicted_host_exits_cleanly_instead_of_aborting(tmp_path):
    """A live-but-slow host that catches up after the survivors already
    scaled down must exit 0 (evicted), never abort the pod out from
    under them."""
    from ddl_tpu.obs import EventWriter, read_events

    w1 = EventWriter(tmp_path / "logs", "evictjob", host=1)
    scripts = {
        0: [FakeChild(rc=1, delay=0.05), FakeChild(rc=0)],
        1: [FakeChild(rc=None), FakeChild(rc=None)],
    }
    results = {}

    def host(i):
        rv = _rv(
            tmp_path / "nas", i, 2,
            timeout_s=(0.4 if i == 0 else 10.0),
        )
        it = iter(scripts[i])
        sup = PodSupervisor(
            lambda epoch, idx: next(it), rv,
            poll_s=0.005, heartbeat_s=0.02, stale_after_s=30.0,
            # host 1 keeps heartbeating but is slow to see signals, so
            # it misses host 0's join barrier (the barrier route, not
            # the staleness route)
            signal_poll_s=(0.05 if i == 0 else 1.5),
            elastic=True,
            backoff=Backoff(base=0.01, jitter=0.0), log=lambda m: None,
            events=(w1 if i == 1 else None),
        )
        results[i] = sup.run()

    threads = [threading.Thread(target=host, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), "evict sim deadlocked"
    # BOTH exit 0: host 0 finished the run alone, host 1 was evicted
    assert results == {0: 0, 1: 0}
    w1.close()
    rv = _rv(tmp_path / "nas", 0, 2)
    assert rv.aborted() is None
    final = rv.epoch_record(rv.current_epoch())
    assert final["hosts"] == [0]
    done = [e for e in read_events(w1.path)
            if e["kind"] == "supervisor_done"]
    assert done and done[-1]["rc"] == 0 and done[-1].get("evicted") is True


def test_join_request_intake_filters_members_and_stale(tmp_path):
    """The leader's view of ``joins/``: a non-member's marker surfaces
    with an age, a member's own leftover marker is void, out-of-range
    hosts are ignored, and stale markers (a joiner that died mid-wait)
    are dropped under ``fresh_s``."""
    rv0 = _rv(tmp_path, 0, 3)
    rv0.adopt_membership([0, 2])  # host 1 was evicted earlier
    rv1 = _rv(tmp_path, 1, 3)
    assert rv0.join_requests() == []
    rv1.publish_join_request(1, note="back")
    (req,) = rv0.join_requests()
    assert req["host"] == 1 and req["epoch"] == 1
    assert req["age"] >= 0.0 and req["note"] == "back"
    # a member's leftover marker is void by definition
    rv2 = _rv(tmp_path, 2, 3)
    rv2.adopt_membership([0, 2])
    rv2.publish_join_request(1)
    assert [r["host"] for r in rv0.join_requests()] == [1]
    # a host outside this launch's [0, n_hosts) is ignored
    (tmp_path / "joins" / "h099.json").write_text(
        json.dumps({"ts": rv0.clock(), "host": 99, "epoch": 0})
    )
    assert [r["host"] for r in rv0.join_requests()] == [1]
    # a stale marker means the joiner went silent after asking
    (tmp_path / "joins" / "h001.json").write_text(
        json.dumps({"ts": rv0.clock() - 60.0, "host": 1, "epoch": 1})
    )
    assert rv0.join_requests(fresh_s=5.0) == []
    assert [r["host"] for r in rv0.join_requests()] == [1]  # unbounded
    # refreshing the marker (the joiner's heartbeat analogue) revives it
    rv1.publish_join_request(2)
    assert [r["host"] for r in rv0.join_requests(fresh_s=5.0)] == [1]
    rv1.clear_join_request()
    assert rv0.join_requests() == []


def test_grow_epoch_ledger_rides_first_writer_wins(tmp_path):
    """A grow proposal is the same atomically-created ledger record as
    a shrink: budgets roll forward unchanged, the record carries the
    LARGER host set, and a racing proposer adopts the winner."""
    rv = _rv(tmp_path, 0, 3)
    rec1 = rv.propose_restart(
        0, "peer_lost", crash=False, preempt=True, rc=EXIT_PREEMPTED,
        hosts=[0, 2],
    )
    rv.adopt_membership(rec1["hosts"])
    assert rv.world == 2
    rec2 = rv.propose_restart(
        1, "peer_join", crash=False, preempt=False, rc=EXIT_PREEMPTED,
        hosts=[0, 1, 2],
    )
    assert rec2["hosts"] == [0, 1, 2] and rec2["world"] == 3
    # a grow is neither a crash nor a preemption; budgets roll forward
    assert rec2["crashes"] == 0 and rec2["preemptions"] == 1
    assert rec2["delay"] == 0.0  # growth relaunches without backoff
    # a racing proposer still on the shrunken membership loses the race
    # and adopts the grown record unchanged (one restart event, one
    # classification — even when the racers disagreed on the reason)
    rv2 = _rv(tmp_path, 2, 3)
    rv2.adopt_membership([0, 2])
    won = rv2.propose_restart(
        1, "peer_stale/crash", crash=True, preempt=False,
        delay_fn=lambda n: 9.9,
    )
    assert won == rec2
    rv2.adopt_membership(won["hosts"])
    assert rv2.world == 3 and rv2.leader == 0


def test_elastic_rejoin_child_leaves_and_is_grown_back(tmp_path):
    """The full scripted grow cycle: host 1's child exits EXIT_REJOIN
    (a voluntary leave, e.g. an injected ``rejoin`` fault), the pod
    shrinks to [0], host 1's supervisor publishes a join_request from
    ``_await_rejoin``, and the leader answers with a ``peer_join``
    epoch whose membership is [0, 1] again.  Both hosts finish at the
    grown world; no budget was burned at any step."""
    scripts = {
        # epoch-0 child killed at the rejoin intent; epoch-1 child
        # (world [0]) killed at the peer_join; epoch-2 child completes
        0: [FakeChild(rc=None), FakeChild(rc=None), FakeChild(rc=0)],
        # epoch-0 child leaves voluntarily; host 1 is not a member of
        # epoch 1, so its next child runs in epoch 2
        1: [FakeChild(rc=EXIT_REJOIN, delay=0.05), FakeChild(rc=0)],
    }
    results = _run_pod(
        tmp_path, [scripts[0], scripts[1]], elastic=True, max_restarts=0,
    )
    assert results == {0: 0, 1: 0}
    assert scripts[0][0].killed and scripts[0][1].killed
    rv = _rv(tmp_path, 0, 2)
    assert rv.aborted() is None
    assert rv.current_epoch() == 2
    rec1, rec2 = rv.epoch_record(1), rv.epoch_record(2)
    assert rec1["reason"] in ("rejoin", "peer_rejoin")
    assert rec1["hosts"] == [0] and rec1["world"] == 1
    assert rec1["rc"] == EXIT_REJOIN
    assert rec1["crashes"] == 0 and rec1["preemptions"] == 0
    assert rec2["reason"] == "peer_join"
    assert rec2["hosts"] == [0, 1] and rec2["world"] == 2
    assert rec2["crashes"] == 0 and rec2["preemptions"] == 0
    # the joiner withdrew its marker once the grow epoch admitted it
    assert rv.join_requests() == []


# ---------------------------------------------------------------------------
# end-to-end: the 3-host pod sim (real trainers, real supervisors)
# ---------------------------------------------------------------------------


def _clean_env() -> dict:
    """The suite's environment minus everything that would leak pod/
    fault/coordination state into a sim's children."""
    return {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "DDL_FAULT",
                     "DDL_FAULT_STATE", "DDL_WATCHDOG_S", "DDL_COORD_DIR",
                     "DDL_COORD_HOSTS", "DDL_COORD_HOST", "DDL_HOST_ID",
                     "DDL_RESTART_EPOCH", "DDL_SUPERVISED",
                     "DDL_OBS_STEP_SPANS", "DDL_COORD_MEMBERS",
                     "DDL_NUM_PROCESSES", "DDL_PROCESS_ID",
                     "DDL_LAUNCH_TOKEN", "DDL_COMPILE_CACHE")
    }


def _read_consumed(sim: Path, host: int) -> list[tuple[int, int]]:
    out = []
    for line in (sim / f"consumed_h{host}.log").read_text().splitlines():
        e, s = line.split()
        out.append((int(e), int(s)))
    return out


def _suite_cache_env() -> dict:
    """The suite's persistent compile cache (tests/conftest.py), placed
    for a sim's children the way any caller places it."""
    import jax

    return {"JAX_COMPILATION_CACHE_DIR": jax.config.jax_compilation_cache_dir}


def _warm_compile_cache(sim_env: dict, tmp_path: Path) -> None:
    """One plain 1-step child run to seed the persistent XLA cache, so
    generation-0 children compile in far less than the watchdog
    deadline."""
    env = dict(sim_env, DDL_SIM_DIR=str(tmp_path / "warmup"),
               DDL_SIM_STEPS="1", DDL_SIM_PACE="0")
    (tmp_path / "warmup").mkdir()
    subprocess.run(
        [sys.executable, str(CHILD)], env=env, check=True, timeout=240,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )


def test_three_host_pod_sim_stall_escalation_and_exact_resume(tmp_path):
    """The acceptance scenario end to end: stall@step on host 1 → its
    watchdog escalates (exit-intent + resumable exit) → ALL THREE hosts
    kill their trainers and relaunch in the same restart epoch → every
    host restores the rank-0-agreed snapshot → identical final step and
    identical final weights on every host, and each host's final
    incarnation consumed exactly the batches from the restored cursor to
    the end — none duplicated, none skipped."""
    from ddl_tpu import checkpoint as ckpt
    from ddl_tpu.supervisor import supervise_pod_command

    sim = tmp_path / "sim"
    nas = tmp_path / "nas"
    sim.mkdir()
    nas.mkdir()
    steps = 10
    base_env = _clean_env()
    base_env.update(
        DDL_SIM_DIR=str(sim),
        DDL_SIM_STEPS=str(steps),
        DDL_SIM_PACE="0.8",
        DDL_JOB_ID="podsim",
        DDL_LOG_DIR=str(sim / "suplogs"),
        DDL_WATCHDOG_S="4",
        **_suite_cache_env(),
    )
    _warm_compile_cache(base_env, tmp_path)

    results = {}

    def host(i):
        env = dict(base_env)
        if i == 1:
            # stall EARLY so the coordinated kill lands mid-run on the
            # healthy hosts (a late kill can let a graceful SIGTERM
            # snapshot complete the whole run — also legal, but the
            # interesting audit is a nonempty resume tail)
            env["DDL_FAULT"] = "stall@step:2:300"  # the hang
        results[i] = supervise_pod_command(
            [sys.executable, str(CHILD)], nas, i, 3,
            env=env, max_restarts=3,
            backoff=Backoff(base=0.01, jitter=0.0),
            poll_s=0.05, heartbeat_s=0.2, stale_after_s=60.0,
            log=lambda m: None,
        )

    threads = [threading.Thread(target=host, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "pod sim deadlocked"
    assert results == {0: 0, 1: 0, 2: 0}, results

    # the rendezvous state is run-scoped: markers live under the
    # launch-token subdir acquire_launch opened for this pod lifetime
    from ddl_tpu.coord import active_launch_root

    launch = active_launch_root(nas)
    assert launch is not None and launch.parent == nas / "launches"
    # the completed launch is closed, so a lone relaunched host cannot
    # rejoin its barriers (it would open a fresh subdir instead)
    assert (launch / "finished.json").is_file()
    rv = _rv(launch, 0, 3)
    # exactly one coordinated restart, triggered by the stalled host
    assert rv.current_epoch() == 1, rv.current_epoch()
    rec = rv.epoch_record(1)
    assert rec["crashes"] == 0  # a hang is resumable, not a crash

    # every host completed IN RESTART EPOCH 1, at the same final step,
    # with bit-identical weights
    finals = []
    for i in range(3):
        last = (sim / f"final_h{i}.log").read_text().splitlines()[-1]
        e, step, digest = last.split()
        finals.append((int(e), int(step), digest))
    assert all(e == 1 for e, _, _ in finals), finals
    assert all(s == steps for _, s, _ in finals), finals
    assert len({d for _, _, d in finals}) == 1, finals

    # exact resume: host 0 published the agreed snapshot through the
    # rendezvous (read the marker directly — rank 0's agree() would
    # recompute); its manifest cursor is the resume step, and every
    # host's final incarnation consumed exactly [cursor .. steps)
    import json

    agreed = json.loads(
        (launch / "agree" / "resume-podsim-e1.json").read_text()
    )["value"]
    # agreed None is a legal race: the coordinated kill can land before
    # any snapshot COMMITTED (the stall fires at step 2; under suite
    # load the healthy hosts may be killed mid-first-save, which
    # integrity checking rightly refuses) — rank 0 then agrees on "no
    # snapshot" and every host retrains from scratch, which the audit
    # below still proves batch-exact
    if agreed is not None:
        cursor = ckpt.read_cursor(sim / "ckpt", "podsim", agreed)
        assert cursor is not None and cursor["step"] == agreed
    resume_from = 0 if agreed is None else agreed
    for i in range(3):
        # the epoch-1 incarnation consumed exactly [resume_from, steps)
        # — empty iff the agreed snapshot already held the completed run
        # (a graceful coordinated-kill snapshot landed at the last step)
        tail = [s for e, s in _read_consumed(sim, i) if e == 1]
        assert tail == list(range(resume_from, steps)), (
            f"h{i} replayed or skipped batches: {tail} "
            f"(agreed resume {agreed})"
        )

    # the live-monitoring surfaces read the pod's shared supervisor
    # stream dir (three per-host files with barrier completion stamps):
    # watch renders a populated frame, export scrapes per-host series
    # including the barrier-fit clock offsets over the shared barriers
    from ddl_tpu.obs.export import prometheus_text
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.watch import build_frame

    fold = fold_job(sim / "suplogs", "podsim", cache=False)
    assert len(fold.streams) == 3
    frame = build_frame(fold, "podsim")
    assert "pod_restart" in frame
    assert "clk_off_s" in frame
    scrape = prometheus_text(fold, "podsim")
    assert "ddl_obs_barrier_wait_seconds_total{" in scrape
    assert "ddl_obs_clock_offset_seconds{" in scrape
    assert scrape.count('ddl_obs_restarts_total{') == 3

    # restart-latency accounting (obs): every relaunched child that
    # trained in epoch 1 stamped its first completed step against the
    # pod-wide restart decision (DDL_RELAUNCH_TS from the epoch
    # record's proposal time) — the relaunch-to-step metric
    from ddl_tpu.obs.events import read_events

    for i in range(3):
        if not [s for e, s in _read_consumed(sim, i) if e == 1]:
            continue  # trained nothing in epoch 1: no first step to stamp
        evs = read_events(
            sim / f"logs_h{i}" / "by_job_id" / "podsim"
            / f"events-h{i:03d}.jsonl"
        )
        rls = [e for e in evs if e.get("kind") == "restart_latency"]
        assert rls, f"h{i} emitted no restart_latency event"
        assert rls[-1].get("repoch") == 1, rls[-1]
        assert rls[-1]["latency"] > 0
        # the decision origin is the epoch record's proposal stamp
        assert rls[-1]["decision_ts"] == pytest.approx(rec["ts"])

    # goodput ledger (round 20) on the real pod-sim streams: every
    # (host, repoch) incarnation's buckets sum EXACTLY to its wall
    # clock, the epoch-1 incarnation's wall starts at the pod-wide
    # restart decision (booking the relaunch gap as restart_gap +
    # barrier), the resumed child's snapshot restore landed in the
    # checkpoint bucket, and warm == cold through the sidecar
    from ddl_tpu.obs.goodput import ledger_from_fold, render_goodput

    for i in range(3):
        logs = sim / f"logs_h{i}"
        f_cold = fold_job(logs, "podsim", cache=False)
        ledger = ledger_from_fold(f_cold)
        assert ledger["incarnations"], f"h{i}: empty goodput ledger"
        for inc in ledger["incarnations"]:
            total = sum(inc["seconds"].values())
            assert total == pytest.approx(inc["wall_s"], abs=1e-9)
            # attribution never meaningfully exceeds the wall (the
            # acceptance's 1% bound on the residual)
            assert inc["seconds"]["untracked"] >= -0.01 * max(
                inc["wall_s"], 1e-9
            ), (i, inc)
        e1 = [a for a in ledger["incarnations"] if a["repoch"] == 1]
        trained_e1 = [s for e, s in _read_consumed(sim, i) if e == 1]
        if e1 and trained_e1:
            acc = e1[0]
            # the decision-anchored window books the relaunch cost
            assert (
                acc["seconds"]["restart_gap"] + acc["seconds"]["barrier"]
            ) > 0, acc
            if agreed is not None:
                assert acc["seconds"]["checkpoint"] > 0, acc
        warm = render_goodput(
            ledger_from_fold(fold_job(logs, "podsim", cache=True)),
            "podsim",
        )
        assert warm == render_goodput(ledger, "podsim")


def test_three_host_pod_sim_permanent_host_loss_elastic_continue(tmp_path):
    """The elastic acceptance e2e: host 1's supervisor makes the start
    barrier, heartbeats once, and dies PERMANENTLY before launching its
    trainer.  The two elastic survivors hold the eviction grace, agree
    restart epoch 1 with membership [0, 2] / world 2 through the epoch
    ledger, relaunch with the respecced bootstrap env
    (``DDL_COORD_MEMBERS=0,2``, survivors renumbered contiguously),
    resume the rank-0-agreed snapshot, and finish with identical final
    weights — the epoch-1 tail consuming exactly [resume, steps) on
    both survivors (no batch lost to the eviction, none replayed)."""
    import json

    from ddl_tpu import checkpoint as ckpt
    from ddl_tpu import coord
    from ddl_tpu.supervisor import supervise_pod_command

    sim = tmp_path / "sim"
    nas = tmp_path / "nas"
    sim.mkdir()
    nas.mkdir()
    steps = 8
    base_env = _clean_env()
    base_env.update(
        DDL_SIM_DIR=str(sim),
        DDL_SIM_STEPS=str(steps),
        DDL_SIM_PACE="0.5",
        DDL_JOB_ID="podelastic",
        DDL_LOG_DIR=str(sim / "suplogs"),
        DDL_WATCHDOG_S="30",
        **_suite_cache_env(),
    )
    _warm_compile_cache(base_env, tmp_path)

    # host 1: the supervisor joins the pod's launch, arrives at the
    # start barrier, beats once as "running" — then dies outright (it
    # never spawns a child and never beats again)
    launch1 = coord.acquire_launch(nas)
    rv1 = Rendezvous(launch1, 1, 3)
    rv1.arrive("start")
    rv1.publish_heartbeat("running", 0)

    results = {}

    def host(i):
        results[i] = supervise_pod_command(
            [sys.executable, str(CHILD)], nas, i, 3,
            env=dict(base_env), max_restarts=3,
            backoff=Backoff(base=0.01, jitter=0.0),
            poll_s=0.05, heartbeat_s=0.2, stale_after_s=1.5,
            elastic=True, elastic_grace_s=1.5,
            log=lambda m: None,
        )

    threads = [threading.Thread(target=host, args=(i,)) for i in (0, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "elastic sim deadlocked"
    assert results == {0: 0, 2: 0}, results

    # all three joined ONE launch; the survivors closed it
    launch = coord.active_launch_root(nas)
    assert launch == launch1
    assert (launch / "finished.json").is_file()
    rv = _rv(launch, 0, 3)
    assert rv.aborted() is None
    assert rv.current_epoch() == 1, rv.current_epoch()
    rec = rv.epoch_record(1)
    assert rec["reason"] == "peer_lost"
    assert rec["hosts"] == [0, 2] and rec["world"] == 2
    assert rec["crashes"] == 0  # losing a host is not a crash

    # both survivors finished IN EPOCH 1, same step, identical weights;
    # the dead host never trained at all
    finals = {}
    for i in (0, 2):
        last = (sim / f"final_h{i}.log").read_text().splitlines()[-1]
        e, step, digest = last.split()
        finals[i] = (int(e), int(step), digest)
    assert all(f == (1, steps, finals[0][2]) for f in finals.values()), finals
    assert not (sim / "final_h1.log").exists()

    # the relaunch env carried the agreed membership and the
    # contiguously-renumbered SPMD bootstrap (the data-axis respec the
    # children's `parallel/rules` world derivation reads)
    for i in (0, 2):
        lines = (sim / f"env_h{i}.log").read_text().splitlines()
        e1 = [ln for ln in lines if ln.startswith("1 ")][-1]
        assert "members=0,2" in e1, e1
        assert "nproc=2" in e1, e1
        assert f"pid={0 if i == 0 else 1}" in e1, e1
        e0 = [ln for ln in lines if ln.startswith("0 ")][0]
        assert "members=0,1,2" in e0 and "nproc=-" in e0, e0

    # exact resume over the agreed snapshot: the epoch-1 incarnations
    # consumed exactly [resume, steps) — agreed None is the legal
    # killed-before-first-commit race (retrain from scratch, still
    # batch-exact)
    agreed = json.loads(
        (launch / "agree" / "resume-podelastic-e1.json").read_text()
    )["value"]
    if agreed is not None:
        cursor = ckpt.read_cursor(sim / "ckpt", "podelastic", agreed)
        assert cursor is not None and cursor["step"] == agreed
    resume_from = 0 if agreed is None else agreed
    for i in (0, 2):
        tail = [s for e, s in _read_consumed(sim, i) if e == 1]
        assert tail == list(range(resume_from, steps)), (
            f"h{i} replayed or skipped batches: {tail} "
            f"(agreed resume {agreed})"
        )


def test_three_host_pod_sim_host_loss_then_rejoin(tmp_path):
    """The elastic scale-UP acceptance e2e, the full churn cycle on
    real trainers: host 1's supervisor dies permanently after the
    start barrier, the survivors evict it and train ON at world 2
    ([0, 2], renumbered) — then a replacement host-1 supervisor starts
    into the shrunken launch, fails membership adoption, publishes a
    join_request, and the leader answers with a ``peer_join`` restart
    epoch at the FULL membership.  All three hosts finish epoch 2 with
    identical final weights: the ZeRO-sharded state crossed dp layouts
    twice (shrink at e1, grow at e2) through the ordinary
    rank-0-agreed restore, and every epoch's consumed tail runs
    exactly [agreed resume, ...) — no batch lost to the churn, none
    replayed within a lineage."""
    from ddl_tpu import checkpoint as ckpt
    from ddl_tpu import coord
    from ddl_tpu.supervisor import supervise_pod_command

    sim = tmp_path / "sim"
    nas = tmp_path / "nas"
    sim.mkdir()
    nas.mkdir()
    steps = 12
    base_env = _clean_env()
    base_env.update(
        DDL_SIM_DIR=str(sim),
        DDL_SIM_STEPS=str(steps),
        DDL_SIM_PACE="0.35",
        DDL_JOB_ID="podrejoin",
        DDL_LOG_DIR=str(sim / "suplogs"),
        DDL_WATCHDOG_S="30",
        **_suite_cache_env(),
    )
    _warm_compile_cache(base_env, tmp_path)

    # host 1 makes the start barrier, beats once as "running" — then
    # its supervisor dies outright (the same loss the elastic-continue
    # e2e pins; this test carries the story through the grow)
    launch1 = coord.acquire_launch(nas)
    rv1 = Rendezvous(launch1, 1, 3)
    rv1.arrive("start")
    rv1.publish_heartbeat("running", 0)

    results = {}

    def host(i):
        results[i] = supervise_pod_command(
            [sys.executable, str(CHILD)], nas, i, 3,
            env=dict(base_env), max_restarts=3,
            backoff=Backoff(base=0.01, jitter=0.0),
            poll_s=0.05, heartbeat_s=0.2, stale_after_s=1.5,
            elastic=True, elastic_grace_s=1.5,
            log=lambda m: None,
        )

    threads = {i: threading.Thread(target=host, args=(i,)) for i in (0, 2)}
    for t in threads.values():
        t.start()

    # the replacement host-1 supervisor starts only once the world-2
    # incarnation has actually TRAINED a batch — the rejoin must
    # interrupt a live shrunken pod mid-run, not race the eviction
    # boundary (an immediate re-grow is legal but would leave the
    # world-2 epoch this test audits without a single step)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            if any(e == 1 for e, _ in _read_consumed(sim, 0)):
                break
        except OSError:
            pass
        time.sleep(0.05)
    else:
        pytest.fail("survivors never trained at world 2")

    threads[1] = threading.Thread(target=host, args=(1,))
    threads[1].start()
    for t in threads.values():
        t.join(timeout=300)
    assert not any(
        t.is_alive() for t in threads.values()
    ), "rejoin sim deadlocked"
    assert results == {0: 0, 1: 0, 2: 0}, results

    launch = coord.active_launch_root(nas)
    assert launch == launch1 and (launch / "finished.json").is_file()
    rv = _rv(launch, 0, 3)
    assert rv.aborted() is None
    assert rv.current_epoch() == 2, rv.current_epoch()
    rec1, rec2 = rv.epoch_record(1), rv.epoch_record(2)
    # epoch 1: the eviction (a preemption-class event, never a crash)
    assert rec1["reason"] == "peer_lost", rec1
    assert rec1["hosts"] == [0, 2] and rec1["world"] == 2
    assert rec1["crashes"] == 0 and rec1["preemptions"] == 1
    # epoch 2: the grow — proposed by the leader off the join_request,
    # burning NO budget of either class
    assert rec2["reason"] == "peer_join", rec2
    assert rec2["hosts"] == [0, 1, 2] and rec2["world"] == 3
    assert rec2["crashes"] == 0 and rec2["preemptions"] == 1
    assert rv.join_requests() == []  # marker withdrawn on admission

    # ALL THREE hosts finished IN EPOCH 2, same step, identical weights
    finals = {}
    for i in range(3):
        last = (sim / f"final_h{i}.log").read_text().splitlines()[-1]
        e, step, digest = last.split()
        finals[i] = (int(e), int(step), digest)
    assert all(
        f == (2, steps, finals[0][2]) for f in finals.values()
    ), finals

    # env audit: epoch 1 ran the renumbered 2-host world on the
    # survivors; epoch 2 dropped the override (back to the full world)
    # on everyone.  Host 1's ONLY incarnation is the epoch-2 one.
    for i in (0, 2):
        lines = (sim / f"env_h{i}.log").read_text().splitlines()
        e1 = [ln for ln in lines if ln.startswith("1 ")][-1]
        assert "members=0,2" in e1 and "nproc=2" in e1, e1
        assert f"pid={0 if i == 0 else 1}" in e1, e1
    lines1 = (sim / "env_h1.log").read_text().splitlines()
    assert all(ln.startswith("2 ") for ln in lines1), lines1
    for i in range(3):
        lines = (sim / f"env_h{i}.log").read_text().splitlines()
        e2 = [ln for ln in lines if ln.startswith("2 ")][-1]
        assert "members=0,1,2" in e2 and "nproc=-" in e2, e2

    # batch-exactness across BOTH churn boundaries: each epoch's tail
    # consumed exactly [agreed resume, ...) — contiguous from the
    # restored cursor, with the final epoch reaching the end
    for ep, hosts in ((1, (0, 2)), (2, (0, 1, 2))):
        agreed = json.loads(
            (launch / "agree" / f"resume-podrejoin-e{ep}.json").read_text()
        )["value"]
        if agreed is not None:
            cursor = ckpt.read_cursor(sim / "ckpt", "podrejoin", agreed)
            assert cursor is not None and cursor["step"] == agreed
        start = 0 if agreed is None else agreed
        for i in hosts:
            tail = [s for e, s in _read_consumed(sim, i) if e == ep]
            assert tail == list(range(start, start + len(tail))), (
                f"h{i} e{ep} replayed or skipped batches: {tail} "
                f"(agreed resume {agreed})"
            )
            if ep == 2:
                assert tail and tail[-1] == steps - 1, (i, tail)

    # observability: the supervisor stream timeline surfaces the whole
    # grow cycle — the joiner's join_request, the leader's peer_join,
    # and per-repoch memberships on the restart markers (the rendered
    # watch frame keeps only the LAST few incidents, so assert over the
    # full folded timeline's labels; the frame itself must carry the
    # grow epoch's membership)
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.pod import _timeline_label, pod_summary_from_fold
    from ddl_tpu.obs.watch import build_frame

    fold = fold_job(sim / "suplogs", "podrejoin", cache=False)
    labels = [
        _timeline_label(e)
        for e in pod_summary_from_fold(fold)["timeline"]
    ]
    assert any(lb.startswith("join_request") for lb in labels), labels
    assert any(lb.startswith("peer_join hosts=[1]") for lb in labels), labels
    assert any(
        "peer_join -> epoch 2" in lb and "hosts=[0, 1, 2]" in lb
        for lb in labels
    ), labels
    frame = build_frame(fold, "podrejoin")
    assert "hosts=[0, 1, 2]" in frame, frame

    # goodput (round 20 ledger): the joiner's grow-epoch incarnation
    # books its relaunch into restart_gap/barrier and its re-shard
    # restore into checkpoint — not into untracked
    from ddl_tpu.obs.goodput import ledger_from_fold

    agreed2 = json.loads(
        (launch / "agree" / "resume-podrejoin-e2.json").read_text()
    )["value"]
    ledger = ledger_from_fold(fold_job(sim / "logs_h1", "podrejoin",
                                       cache=False))
    e2_inc = [a for a in ledger["incarnations"] if a["repoch"] == 2]
    assert e2_inc, ledger["incarnations"]
    acc = e2_inc[0]
    assert sum(acc["seconds"].values()) == pytest.approx(
        acc["wall_s"], abs=1e-9
    )
    assert acc["seconds"]["untracked"] >= -0.01 * max(acc["wall_s"], 1e-9)
    assert (acc["seconds"]["restart_gap"] + acc["seconds"]["barrier"]) > 0
    if agreed2 is not None:
        assert acc["seconds"]["checkpoint"] > 0, acc
