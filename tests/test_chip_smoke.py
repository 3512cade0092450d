"""chip_smoke.py rehearsals (CPU backend, tiny corpus, in-process), the
compile-cache placement rule, and the refusals that keep the device from
being hidden: no accelerator, two device owners."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _run_smoke(argv, capsys, monkeypatch, tmp_path):
    import chip_smoke

    # cache placed from outside: the helper then sets nothing in code,
    # so this worker's later tests keep their jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = chip_smoke.main(argv)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    return rc, lines


def test_rehearsal_runs_the_one_chip_flow(capsys, monkeypatch, tmp_path):
    rc, lines = _run_smoke(["--rehearse", "--subs", "6000"],
                           capsys, monkeypatch, tmp_path)
    last = lines[-1]
    assert rc == 0, [ln for ln in lines if ln.get("check") == "FAILED"]
    # a rehearsal never prints the TPU last line
    assert last == {"ok": True, "rehearsal": True,
                    "device": last["device"]}
    assert last["device"]["platform"] == "cpu"
    # a burst sent again (its window void on a busy machine) prints
    # "name#2": the asserted attempt is the last line with its counts
    by_phase = {ln["phase"].split("#")[0]: ln for ln in lines
                if "phase" in ln and ("published" in ln
                                      or not ln["phase"].startswith((
                                          "single_batch", "fat_connection",
                                          "super_batch", "delta_")))}
    assert by_phase["device"]["compile_cache_dir"] == str(tmp_path)
    assert by_phase["boot1:load"]["through"] == "Registry.subscribe"
    assert by_phase["boot1:warm"]["ladder_complete"]
    # the counters the chip run asserts on: device-served, nothing
    # host-served, one fold_many super-dispatch, deltas by scatter
    c = by_phase["counters"]
    assert c["match_publishes"] > 0 and c["super_dispatches"] >= 1
    assert all(c[k] == 0 for k in (
        "busy_host_pubs", "degraded_host_pubs", "stalled_host_pubs",
        "expired_host_pubs", "rebuild_host_pubs", "overload_host_pubs",
        "warm_failures", "device_failures"))
    assert c["breaker"] == "closed"
    for ph in ("single_batch_9", "fat_connection", "delta_subscribe",
               "delta_unsubscribe"):
        assert by_phase[ph]["device_served"] == by_phase[ph]["published"]
    assert by_phase["fat_connection"]["connections"] == 1
    # the broker under test runs as shipped: no protection loosened
    from vernemq_tpu.broker.config import DEFAULTS

    load = by_phase["boot1:load"]
    for knob in ("sysmon_lag_threshold", "overload_dispatch_budget_ms"):
        assert load[knob] == DEFAULTS[knob]
    # one boot; JAX's own cache counters say what the ladder compiled
    assert not any(p.startswith("boot2") for p in by_phase)
    cache = by_phase["boot1:warm"]["compile_cache"]
    assert {"requests", "hits", "misses", "dir_entries",
            "dir_bytes"} <= set(cache)


def _fake_rig():
    import types

    return types.SimpleNamespace(
        matcher=types.SimpleNamespace(_warming=False), seq=0, void_below=0)


def _moved(**kw):
    import chip_smoke

    d = dict.fromkeys(chip_smoke.HOST_SERVED, 0)
    d.update(busy_sheds=0, super_dispatches=0, lag_events=0,
             governor_rose=0, qos0_shed=0)
    d.update(kw)
    return d


@pytest.mark.asyncio
async def test_void_attempt_forgives_only_what_a_protection_explains(
        monkeypatch):
    """A cold super-batch shape is warm-up traffic and the burst is sent
    again — but wrong rows or a stray message on that attempt still fail
    the run, whatever the next attempt does."""
    import chip_smoke

    attempts = []

    async def fake_run_burst(rig, chk, name, topics, expect_super=False,
                             window_chk=None, **_kw):
        attempts.append(name)
        rig.seq += 10
        if len(attempts) == 1:  # met a cold K: shed while it compiled
            window_chk.check(False, f"{name}: no publish host-served")
            chk.check(False, f"{name}: device rows equal the trie's")
            return _moved(busy_host_pubs=5, busy_sheds=1)
        return _moved(super_dispatches=1)

    monkeypatch.setattr(chip_smoke, "run_burst", fake_run_burst)
    rig = _fake_rig()
    chk = chip_smoke.Checks()
    await chip_smoke.asserted_burst(rig, chk, "super_batch", list,
                                    expect_super=True)
    assert attempts == ["super_batch", "super_batch#2"]
    assert chk.failed == ["super_batch: device rows equal the trie's"]
    # late deliveries of the void attempt are told by sequence number
    assert rig.void_below == 10
    # the last attempt forgives nothing
    attempts.clear()
    chk = chip_smoke.Checks()

    async def always_cold(rig, chk, name, topics, window_chk=None, **_kw):
        attempts.append(name)
        window_chk.check(False, f"{name}: no publish host-served")
        return _moved(busy_host_pubs=5, busy_sheds=1)

    monkeypatch.setattr(chip_smoke, "run_burst", always_cold)
    await chip_smoke.asserted_burst(rig, chk, "super_batch", list,
                                    attempts=2, expect_super=True)
    assert chk.failed == ["super_batch#2: no publish host-served"]


@pytest.mark.asyncio
@pytest.mark.parametrize("first,sent_again", [
    # queued publishes expired while the loop-lag alarm went off
    (dict(expired_host_pubs=2688, lag_events=1), True),
    # the governor rose (level 2 drops QoS0) inside the window
    (dict(qos0_shed=900, governor_rose=1), True),
    # the same with no alarm of the broker's: a failure
    (dict(expired_host_pubs=2688), False),
    # an alarm never explains a watchdog stall or a rebuild
    (dict(stalled_host_pubs=3, lag_events=1), False),
    (dict(rebuild_host_pubs=3, governor_rose=1), False),
])
async def test_window_is_void_only_with_the_brokers_own_alarm(
        monkeypatch, first, sent_again):
    """What a stall of the whole process produces by design — expiry to
    the trie, QoS0 shed — fails the window unless the broker's own
    loop-lag alarm or overload governor went off inside it (a shared
    host took the cores away): then the burst is sent again."""
    import chip_smoke

    rig = _fake_rig()
    names = []

    async def fake_run_burst(rig, chk, name, topics, window_chk=None, **_kw):
        names.append(name)
        if len(names) == 1:
            window_chk.check(False, f"{name}: no publish host-served")
            return _moved(**first)
        return _moved()

    monkeypatch.setattr(chip_smoke, "run_burst", fake_run_burst)
    chk = chip_smoke.Checks()
    await chip_smoke.asserted_burst(rig, chk, "single_batch_4096", list)
    if sent_again:
        assert names == ["single_batch_4096", "single_batch_4096#2"]
        assert chk.failed == []
    else:
        assert names == ["single_batch_4096"]
        assert chk.failed == ["single_batch_4096: no publish host-served"]


@pytest.mark.slow
@pytest.mark.parametrize("hold_s,expect", [
    (1.8, None),                 # lag alarm: expiry or QoS0 shed (L1/L2)
    (3.2, "reconnected"),        # level 3: the heaviest talkers dropped
])
def test_rehearsal_survives_a_frozen_process(tmp_path, hold_s, expect):
    """The drill the void-window rule exists for: the whole process
    frozen (SIGSTOP, as when a shared host takes the cores away) inside
    the super-burst's window. The broker's protections fire as built,
    the window is void, the burst is sent again and the run passes."""
    import signal
    import subprocess
    import threading
    import time

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--rehearse",
         "--subs", "6000"], stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env)

    def freeze():
        time.sleep(0.4)  # the next phase's burst is in flight by then
        os.kill(p.pid, signal.SIGSTOP)
        time.sleep(hold_s)
        os.kill(p.pid, signal.SIGCONT)

    lines = []
    for ln in p.stdout:
        lines.append(json.loads(ln))
        if (lines[-1].get("phase") == "fat_connection"
                and "published" in lines[-1]):
            threading.Thread(target=freeze, daemon=True).start()
    assert p.wait() == 0, [ln for ln in lines if ln.get("check")]
    assert lines[-1]["ok"] is True
    void = [ln for ln in lines if ln.get("void")]
    assert void and void[0]["phase"] == "super_batch"
    if expect:
        assert any(expect in ln for ln in lines)


def test_rehearsal_four_chip_phase_only(capsys, monkeypatch, tmp_path):
    rc, lines = _run_smoke(["--rehearse", "--chips", "4", "--subs", "20000"],
                           capsys, monkeypatch, tmp_path)
    assert rc == 0, [ln for ln in lines if ln.get("check") == "FAILED"]
    assert lines[-1]["rehearsal"] is True
    phases = [ln["phase"] for ln in lines if "phase" in ln]
    # only the mesh phase and what it is compared with
    assert "mesh_vs_single" in phases and "mesh_status" in phases
    assert not any(p.startswith(("single_batch", "super_batch", "delta"))
                   for p in phases)
    st = next(ln for ln in lines if ln.get("phase") == "mesh_status")
    assert st["slices"] == 4 and all(r > 0 for r in st["rows_per_slice"])


def test_refuses_without_a_tpu(capsys, monkeypatch, tmp_path):
    """No --rehearse on the CPU backend: "ok": false, non-zero exit, and
    nothing ran."""
    rc, lines = _run_smoke([], capsys, monkeypatch, tmp_path)
    assert rc != 0
    assert lines[-1]["ok"] is False
    assert "no TPU" in lines[-1]["failed"][0]
    assert [ln.get("phase") for ln in lines[:-1]] == ["device"]


def test_compile_cache_dir_from_env_sets_nothing(monkeypatch, tmp_path):
    import jax

    from vernemq_tpu.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    assert cc.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    import jax

    from vernemq_tpu.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    try:
        assert cc.configure_compile_cache() == os.path.join(ROOT,
                                                            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
        assert cc.configure_compile_cache() == cc.DEFAULT_DIR  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("kw,match", [
    (dict(default_reg_view="tpu"), "each open the accelerator"),
    (dict(match_service=True, match_view="tpu"),
     "already initialised a JAX backend"),
])
def test_worker_group_refuses_two_device_owners(monkeypatch, kw, match):
    """Off the CPU platform a WorkerGroup lets ONE process own the
    device: not every worker, and not a service child under a parent
    that already holds the backend."""
    import jax

    from vernemq_tpu.broker.workers import WorkerGroup

    jax.devices()  # this process holds a backend
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    g = WorkerGroup(2, port=0, **kw)
    with pytest.raises(RuntimeError, match=match):
        g.start()
    assert not g._procs and g._service_proc is None
