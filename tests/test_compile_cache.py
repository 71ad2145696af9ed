"""Persistent compile cache (utils/compile_cache.py): warm restarts.

Host-tier tests of the activation logic — a directory placed through
JAX_COMPILATION_CACHE_DIR is used as it stands, otherwise root
precedence (arg > env > pod-agreed default > the checkout's fixed
directory), the off switch, topology keying, warm/cold entry counting,
hit/miss counters via jax.monitoring, and the compile_cache obs event.
XLA's own persistence is not under test here (the pod-sim e2e exercises
it via the suite cache); what is under test is that the launch path
points JAX at one agreed, keyed directory and reports the truth about
it.
"""

import json
import os
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from ddl_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Each test starts deactivated with zeroed counters, and the global
    jax config the module mutates is restored afterwards."""
    monkeypatch.setattr(cc, "_active", None)
    monkeypatch.setattr(
        cc, "_counters",
        {"hits": 0, "misses": 0, "evicted": 0, "evicted_bytes": 0},
    )
    # the tests below hold whatever the caller's environment places
    monkeypatch.delenv(cc.ENV_JAX_CACHE, raising=False)
    monkeypatch.delenv(cc.ENV_CACHE, raising=False)
    monkeypatch.delenv(cc.ENV_CACHE_MIN_S, raising=False)
    monkeypatch.delenv(cc.ENV_CACHE_MAX_BYTES, raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    compilation_cache.reset_cache()  # back onto the suite's directory


def test_default_is_the_fixed_checkout_dir_and_off_wins(tmp_path, monkeypatch):
    # bare local run: no env, no rendezvous -> the checkout's directory,
    # the same on every call
    assert str(cc.default_cache_root()) == os.path.join(REPO, ".jax_cache")
    first = cc.activate_compile_cache()
    second = cc.activate_compile_cache()
    want = str(cc.default_cache_root() / cc.topology_key())
    assert first["dir"] == second["dir"] == want
    assert first["placed"] is False
    assert jax.config.jax_compilation_cache_dir == want
    # the force-disable beats even an explicit root
    for off in ("off", "0", ""):
        monkeypatch.setenv(cc.ENV_CACHE, off)
        assert cc.activate_compile_cache(cache_root=tmp_path) is None


_CHILD = """
import json, os
from ddl_tpu.utils import compile_cache as cc
stats = cc.activate_compile_cache()
import jax
print(json.dumps({"pid": os.getpid(), "dir": stats["dir"],
                  "placed": stats["placed"],
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _activate_in_child(env_overrides: dict) -> dict:
    env = {
        k: v for k, v in os.environ.items()
        if k not in (cc.ENV_JAX_CACHE, cc.ENV_CACHE, "XLA_FLAGS")
    }
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, check=True, timeout=120,
        capture_output=True, text=True, cwd=REPO,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_default_dir_is_identical_across_processes():
    a, b = _activate_in_child({}), _activate_in_child({})
    assert a["pid"] != b["pid"]
    assert a["dir"] == b["dir"] == a["config"] == b["config"]
    assert a["dir"].startswith(str(cc.default_cache_root()))


def test_placed_dir_is_used_as_it_stands(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: exactly that directory — nothing
    appended, DDL_COMPILE_CACHE and an explicit root do not override it,
    and the byte bound never evicts from it."""
    placed = tmp_path / "placed"
    placed.mkdir()
    entry = _entry(tmp_path, "placed", "kept", size=1000, age_s=9000)
    monkeypatch.setenv(cc.ENV_JAX_CACHE, str(placed))
    monkeypatch.setenv(cc.ENV_CACHE, str(tmp_path / "ddl"))
    monkeypatch.setenv(cc.ENV_CACHE_MAX_BYTES, "10")
    before = jax.config.jax_compilation_cache_dir
    stats = cc.activate_compile_cache(cache_root=tmp_path / "arg")
    assert stats["dir"] == str(placed) and stats["placed"] is True
    assert stats["entries_before"] == 1 and stats["warm"] is True
    # the program set no directory: jax.config is as JAX made it from
    # the environment (this process started without the variable)
    assert jax.config.jax_compilation_cache_dir == before
    assert entry.exists() and cc.cache_stats()["evicted"] == 0
    assert [p.name for p in placed.iterdir()] == ["kept"]
    assert not (tmp_path / "ddl").exists() and not (tmp_path / "arg").exists()
    # a process STARTED with the variable: jax.config equals it
    child = _activate_in_child({cc.ENV_JAX_CACHE: str(placed)})
    assert child["dir"] == child["config"] == str(placed)
    assert child["placed"] is True
    assert [p.name for p in placed.iterdir()] == ["kept"]


def test_env_activation_keys_by_topology_and_counts_entries(
    tmp_path, monkeypatch
):
    monkeypatch.setenv(cc.ENV_CACHE, str(tmp_path))
    monkeypatch.setenv(cc.ENV_CACHE_MIN_S, "0")
    stats = cc.activate_compile_cache()
    assert stats is not None
    key = cc.topology_key()
    assert key.startswith("cpu-d") and key.endswith(
        f"-p{jax.process_count()}"
    )
    assert stats["key"] == key
    assert stats["dir"] == str(tmp_path / key)
    assert stats["entries_before"] == 0 and stats["warm"] is False
    assert stats["agreed"] is False
    # jax was actually pointed at the keyed dir with the min-compile
    # override
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / key)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # a second incarnation finding entries reports warm
    (tmp_path / key / "xla_exec_a").write_bytes(b"x")
    (tmp_path / key / "xla_exec_b").write_bytes(b"x")
    stats2 = cc.activate_compile_cache()
    assert stats2["entries_before"] == 2 and stats2["warm"] is True


def test_pod_agreed_default_sits_beside_launches(tmp_path):
    from ddl_tpu.coord import Rendezvous

    # the rendezvous root is coord_dir/launches/<token>; the agreed
    # default must OUTLIVE launches: <coord_dir>/compile_cache
    launch = tmp_path / "pod" / "launches" / "l0"
    rv = Rendezvous(launch, 0, 1)
    stats = cc.activate_compile_cache(rv=rv)
    assert stats is not None and stats["agreed"] is True
    assert stats["dir"] == str(
        tmp_path / "pod" / "compile_cache" / stats["key"]
    )


def test_explicit_root_beats_pod_default(tmp_path):
    from ddl_tpu.coord import Rendezvous

    launch = tmp_path / "pod" / "launches" / "l0"
    rv = Rendezvous(launch, 0, 1)
    stats = cc.activate_compile_cache(rv=rv, cache_root=tmp_path / "mine")
    assert stats["dir"].startswith(str(tmp_path / "mine"))


def test_hit_miss_counters_and_event_emission(tmp_path, monkeypatch):
    monkeypatch.setenv(cc.ENV_CACHE, str(tmp_path))

    class Events:
        def __init__(self):
            self.emitted = []

        def emit(self, kind, **fields):
            self.emitted.append((kind, fields))

    ev = Events()
    stats = cc.activate_compile_cache(events=ev)
    assert stats is not None
    # activation emitted one compile_cache event carrying the stats
    assert ev.emitted and ev.emitted[0][0] == "compile_cache"
    assert ev.emitted[0][1]["warm"] is False
    # the monitoring listener counts persistent-cache hit/miss events
    before = dict(cc._counters)
    try:
        from jax import monitoring

        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
    except Exception:
        pytest.skip("jax.monitoring.record_event unavailable")
    live = cc.cache_stats()
    assert live["hits"] == before["hits"] + 1
    assert live["misses"] == before["misses"] + 1
    # re-emission reports the live counters
    cc.emit_cache_event(ev)
    assert ev.emitted[-1][1]["hits"] == live["hits"]


def _entry(root, key, name, size=100, age_s=None):
    """One fake cache entry of ``size`` bytes, optionally backdated."""
    import os
    import time

    p = root / key / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_bytes(b"x" * size)
    if age_s is not None:
        t = time.time() - age_s
        os.utime(p, (t, t))
    return p


def test_eviction_bounds_root_lru_by_mtime(tmp_path):
    # four 100-byte entries across two topology keys, oldest first
    old1 = _entry(tmp_path, "tpu-d8-p2", "a", age_s=4000)
    old2 = _entry(tmp_path, "tpu-d8-p2", "b", age_s=3000)
    new1 = _entry(tmp_path, "cpu-d8-p1", "c", age_s=2000)
    new2 = _entry(tmp_path, "cpu-d8-p1", "d", age_s=1000)
    # no bound configured -> unbounded, nothing touched
    assert cc.evict_to_byte_bound(tmp_path) is None
    assert all(p.exists() for p in (old1, old2, new1, new2))
    # under the bound -> a report, but zero evictions
    res = cc.evict_to_byte_bound(tmp_path, max_bytes=1000)
    assert res == {
        "evicted": 0, "evicted_bytes": 0,
        "total_bytes": 400, "max_bytes": 1000,
    }
    # over the bound -> LRU across keys: exactly the two oldest go
    res = cc.evict_to_byte_bound(tmp_path, max_bytes=250)
    assert res["evicted"] == 2 and res["evicted_bytes"] == 200
    assert res["total_bytes"] == 200
    assert not old1.exists() and not old2.exists()
    assert new1.exists() and new2.exists()
    # the counters accumulate across calls (they ride cache_stats)
    assert cc._counters["evicted"] == 2
    assert cc._counters["evicted_bytes"] == 200


def test_eviction_never_strands_active_keys_fresh_entries(tmp_path):
    # the active key's FRESH entries (this incarnation's warm restart)
    # are held back even when the bound cannot otherwise be met; its
    # stale entries are ordinary LRU fodder
    fresh1 = _entry(tmp_path, "cpu-d8-p1", "fresh1")
    fresh2 = _entry(tmp_path, "cpu-d8-p1", "fresh2")
    stale = _entry(tmp_path, "cpu-d8-p1", "stale", age_s=4000)
    other = _entry(tmp_path, "tpu-d8-p2", "other", age_s=500)
    res = cc.evict_to_byte_bound(
        tmp_path, active_key="cpu-d8-p1", max_bytes=150
    )
    # the stale active entry and the other key's entry were evictable;
    # the two fresh active entries survive even though 200b > 150b
    assert not stale.exists() and not other.exists()
    assert fresh1.exists() and fresh2.exists()
    assert res["evicted"] == 2 and res["total_bytes"] == 200


def test_activation_applies_byte_bound_and_reports_evictions(
    tmp_path, monkeypatch
):
    monkeypatch.setenv(cc.ENV_CACHE, str(tmp_path))
    monkeypatch.setenv(cc.ENV_CACHE_MAX_BYTES, "250")
    key = cc.topology_key()
    kept1 = _entry(tmp_path, key, "warm_a")
    kept2 = _entry(tmp_path, key, "warm_b")
    for n in ("x", "y", "z"):
        _entry(tmp_path, "tpu-d256-p32", n, age_s=4000)

    class Events:
        def __init__(self):
            self.emitted = []

        def emit(self, kind, **fields):
            self.emitted.append((kind, fields))

    ev = Events()
    stats = cc.activate_compile_cache(events=ev)
    # the stale key was evicted to meet the bound; the active key's
    # fresh entries survived, so the restart is STILL warm
    assert kept1.exists() and kept2.exists()
    assert not (tmp_path / "tpu-d256-p32" / "x").exists()
    assert stats["entries_before"] == 2 and stats["warm"] is True
    live = cc.cache_stats()
    assert live["evicted"] == 3 and live["evicted_bytes"] == 300
    # the eviction counters ride the compile_cache obs event
    assert ev.emitted[0][0] == "compile_cache"
    assert ev.emitted[0][1]["evicted"] == 3


def test_no_unguarded_cache_dir_site_in_the_program():
    """The acceptance grep, kept as a test: the only code that sets the
    cache directory is the branch that runs with the variable unset."""
    sites = []
    for root in ("ddl_tpu", "bench.py", "chip_smoke.py", "examples"):
        path = os.path.join(REPO, root)
        files = [path] if path.endswith(".py") else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py")
        ]
        for f in files:
            with open(f) as fh:
                for n, line in enumerate(fh, 1):
                    if '"jax_compilation_cache_dir"' in line:
                        sites.append((os.path.relpath(f, REPO), n))
    assert [f for f, _ in sites] == ["ddl_tpu/utils/compile_cache.py"], sites
