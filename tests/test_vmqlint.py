"""vmqlint suite tests: fixture corpus per pass, mutation tests
(seeded defects must be caught; stripping a real allow-marker must
flip the tree red), JSON output, shim compat, exit-code contract.

The lock-discipline fixtures reconstruct the PR 9 ``adopt_slices`` and
PR 10 ``device_put``-under-the-engine-lock bugs verbatim in shape —
the pass exists because those shipped and were re-fixed by hand; the
corpus pins that it would have caught them.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from tools.vmqlint import core

ROOT = core.REPO_ROOT
SNIP = "vernemq_tpu/_vmqlint_fixture.py"


@pytest.fixture(scope="module")
def base_files():
    """One parse of the real tree shared by every test (the framework's
    own per-run cache, reused across runs here)."""
    return core.collect_files(ROOT)


def run_pass(name, base, overrides=None, paths=None):
    findings, _ = core.run(passes=[name], files=base,
                           overrides=overrides, paths=paths)
    return findings


def snippet_findings(name, base, src, paths_only=True):
    return [f for f in run_pass(name, base, overrides={SNIP: src},
                                paths=[SNIP] if paths_only else None)
            if f.rel == SNIP]


# ------------------------------------------------------------ tree status

def test_tree_is_clean(base_files):
    findings, stats = core.run(files=base_files)
    assert findings == [], [f.render() for f in findings]
    assert stats["passes"] == ["blocking", "metrics", "lock-discipline",
                              "thread-lifecycle", "knob-registry",
                              "fault-registry", "events-registry"]


# -------------------------------------------------- lock-discipline corpus

#: the PR 10 bug, reconstructed: filters/engine.py uploaded the predicate
#: table to the device INSIDE the engine lock — a wedged transfer parked
#: the event loop's _tick/replay/status takers behind the lock
PR10_DEVICE_PUT_UNDER_LOCK = '''
import threading
import jax

class FilterEngine:
    def __init__(self):
        self._lock = threading.Lock()
        self._host_rows = []
        self._dev = None

    def _sync_device(self):
        with self._lock:
            rows = self._pack(self._host_rows)
            self._dev = jax.device_put(rows)   # the shipped defect

    def _pack(self, rows):
        return rows
'''

#: the PR 9 bug, reconstructed: adopt_slices ran device placement under
#: the matcher lock from a gossip callback — a long device flush parked
#: every session this loop serves
PR9_ADOPT_SLICES_UNDER_LOCK = '''
import threading
import jax

class MeshTpuMatcher:
    def __init__(self):
        self.lock = threading.Lock()
        self._slices = {}

    def adopt_slices(self, slices, epoch):
        with self.lock:
            for s in slices:
                self._slices[s] = epoch
            arrs = jax.device_put(self._collect(slices))  # the defect
            self._install(arrs)

    def _collect(self, s):
        return s

    def _install(self, a):
        pass
'''

#: the PR 2 bug shape: compiling the delta ladder while holding the
#: matcher lock — every publish parks behind XLA
PR2_COMPILE_UNDER_LOCK = '''
import threading

class TpuMatcher:
    def __init__(self):
        self.lock = threading.Lock()

    def start(self):
        with self.lock:
            self.warm_delta_ladder(128)
            self.ensure_warm(8)

    def warm_delta_ladder(self, n):
        pass

    def ensure_warm(self, b):
        pass
'''

AWAIT_UNDER_LOCK = '''
import threading

class Collector:
    def __init__(self):
        self._lock = threading.Lock()

    async def flush(self):
        with self._lock:
            await self._dispatch()

    async def _dispatch(self):
        pass
'''


@pytest.mark.parametrize("src,needle", [
    (PR10_DEVICE_PUT_UNDER_LOCK, "device_put"),
    (PR9_ADOPT_SLICES_UNDER_LOCK, "device_put"),
    (PR2_COMPILE_UNDER_LOCK, "warm_delta_ladder"),
    (AWAIT_UNDER_LOCK, "await while holding"),
], ids=["pr10-device-put", "pr9-adopt-slices", "pr2-compile",
        "await-under-lock"])
def test_lock_discipline_catches_reconstructed_bugs(base_files, src,
                                                    needle):
    found = snippet_findings("lock-discipline", base_files, src)
    assert found, f"pass missed the seeded defect ({needle})"
    assert any(needle in f.message for f in found)


def test_lock_discipline_clean_shapes_pass(base_files):
    """The FIXED shapes (snapshot under the lock, transfer outside;
    nested closures run elsewhere) raise nothing."""
    src = '''
import threading
import jax

class FilterEngine:
    def __init__(self):
        self._lock = threading.Lock()
        self._host_rows = []
        self._dev = None

    def _sync_device(self):
        with self._lock:
            rows = self._pack(self._host_rows)   # snapshot only
        self._dev = jax.device_put(rows)         # transfer OUTSIDE

    def _spawn(self):
        with self._lock:
            def _run():
                jax.device_put([1])              # runs later, unheld
            return _run

    def _pack(self, rows):
        return rows
'''
    assert snippet_findings("lock-discipline", base_files, src) == []


def test_lock_discipline_marker_flip(base_files):
    """An annotated deliberate site is suppressed; stripping the marker
    flips it red (the mutation the suite's discipline rests on)."""
    marked = PR10_DEVICE_PUT_UNDER_LOCK.replace(
        "# the shipped defect",
        "# vmqlint: allow(lock-discipline): fixture — host-backed "
        "fake device, transfer is a no-op")
    assert snippet_findings("lock-discipline", base_files, marked) == []
    assert snippet_findings("lock-discipline", base_files,
                            PR10_DEVICE_PUT_UNDER_LOCK)


# ------------------------------------------------- thread-lifecycle corpus

THREAD_NO_JOIN = '''
import threading

class Rebuilder:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        pass

    def close(self):
        pass  # forgets the join
'''

THREAD_NAKED_START = '''
import threading

class Warmer:
    def warm(self):
        threading.Thread(target=self._w, daemon=True).start()

    def _w(self):
        pass

    def close(self):
        pass
'''

THREAD_JOINED_OK = '''
import threading

class Monitor:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        pass

    def stop(self):
        t = self._t
        t.join(timeout=2.0)
'''

TIMER_CANCELLED_OK = '''
import threading

class Flusher:
    def arm(self):
        self._timer = threading.Timer(0.2, self._fire)
        self._timer.start()

    def _fire(self):
        pass

    def close(self):
        self._timer.cancel()
'''

THREAD_POOL_JOINED_OK = '''
import threading

class Pool:
    def __init__(self):
        self._threads = []

    def spawn(self):
        t = threading.Thread(target=self._run, daemon=True)
        self._threads.append(t)
        t.start()

    def _run(self):
        pass

    def close(self):
        for t in self._threads:
            t.join(timeout=1.0)
'''


def test_thread_lifecycle_catches_seeded_defects(base_files):
    for src in (THREAD_NO_JOIN, THREAD_NAKED_START):
        assert snippet_findings("thread-lifecycle", base_files, src), src


def test_thread_lifecycle_accepts_owned_threads(base_files):
    for src in (THREAD_JOINED_OK, TIMER_CANCELLED_OK,
                THREAD_POOL_JOINED_OK):
        assert snippet_findings("thread-lifecycle", base_files,
                                src) == [], src


def test_thread_lifecycle_join_must_be_reachable_from_close(base_files):
    """A join parked in a helper nothing on the teardown path calls
    does not count; one reached THROUGH a teardown helper does."""
    unreachable = ('import threading\n'
                   'class R:\n'
                   '    def start(self):\n'
                   '        self._t = threading.Thread(target=self._r)\n'
                   '        self._t.start()\n'
                   '    def _r(self):\n'
                   '        pass\n'
                   '    def drain(self):  # never called from close()\n'
                   '        self._t.join()\n'
                   '    def close(self):\n'
                   '        pass\n')
    found = snippet_findings("thread-lifecycle", base_files,
                             unreachable)
    assert any("reachable" in f.message for f in found)
    reachable = unreachable.replace(
        '    def close(self):\n        pass\n',
        '    def close(self):\n        self.drain()\n')
    assert snippet_findings("thread-lifecycle", base_files,
                            reachable) == []


def test_thread_lifecycle_unstarted_thread_not_flagged(base_files):
    """A constructed-but-never-started Thread needs no join (joining
    an unstarted Thread raises RuntimeError) — only started handles
    demand a reachable wind-down."""
    src = ('import threading\n'
           'class Lazy:\n'
           '    def __init__(self):\n'
           '        self._t = threading.Thread(target=self._r)\n'
           '    def _r(self):\n'
           '        pass\n'
           '    def close(self):\n'
           '        pass\n')
    assert snippet_findings("thread-lifecycle", base_files, src) == []
    started = src.replace(
        '    def _r(self):',
        '    def go(self):\n        self._t.start()\n'
        '    def _r(self):')
    assert snippet_findings("thread-lifecycle", base_files, started)


def test_knob_registry_annassign_taint(base_files):
    """`cfg: Config = self.config` (AnnAssign) is config-shaped: its
    phantom reads are flagged and its real reads count."""
    src = ('class X:\n'
           '    def f(self):\n'
           '        cfg: Config = self.broker.config\n'
           '        return cfg.get("tpu_breker_enabled", True)\n')
    found = snippet_findings("knob-registry", base_files, src,
                             paths_only=False)
    assert any("tpu_breker_enabled" in f.message for f in found)


def test_knob_registry_set_is_not_a_read(base_files):
    """A knob that is only ever WRITTEN (cfg.set from a plumbing path)
    stays flagged dead — write-only is exactly the plumbed-never-
    consumed defect; and an unrelated dict's .get of the same spelling
    does not launder it."""
    rel = "vernemq_tpu/broker/config.py"
    mutated = base_files[rel].text.replace(
        '"allow_anonymous": False,',
        '"allow_anonymous": False,\n    "vmqlint_writeonly_knob": 7,',
        1)
    writer = ('class P:\n'
              '    def plumb(self, broker, d):\n'
              '        broker.config.set("vmqlint_writeonly_knob", 1)\n'
              '        return d.get("vmqlint_writeonly_knob")\n')
    found = run_pass("knob-registry", base_files,
                     overrides={rel: mutated, SNIP: writer})
    assert any("vmqlint_writeonly_knob" in f.message
               and "never read" in f.message for f in found)


def test_knob_registry_real_marker_flip(base_files):
    """The `workers` knob is read via the RAW conf probe (a read the
    taint walk can't see) and carries the annotation; stripping it
    flips the tree red."""
    rel = "vernemq_tpu/broker/config.py"
    stripped = base_files[rel].text.replace(
        "vmqlint: allow(knob-registry)", "marker stripped")
    found = run_pass("knob-registry", base_files,
                     overrides={rel: stripped})
    assert any("'workers'" in f.message for f in found)


def test_thread_lifecycle_real_marker_flip(base_files):
    """Every real annotated site in the tree (the cooperative-stop
    rebuild threads, the sacrificial executor, the fire-and-forget warm
    threads) flips red when its marker is stripped."""
    sites = [rel for rel, sf in base_files.items()
             if rel.startswith("vernemq_tpu/")
             and "vmqlint: allow(thread-lifecycle)" in sf.text]
    assert sites, "expected annotated thread-lifecycle sites in-tree"
    for rel in sites:
        stripped = base_files[rel].text.replace(
            "vmqlint: allow(thread-lifecycle)", "marker stripped")
        found = run_pass("thread-lifecycle", base_files,
                         overrides={rel: stripped}, paths=[rel])
        assert any(f.rel == rel for f in found), rel


# --------------------------------------------------------- blocking corpus

BLOCKING_SNIPPET = '''
import time

async def handler():
    time.sleep(0.1)
    open("/tmp/x")
    fut.result()
'''


def test_blocking_catches_and_marker_flips(base_files):
    found = snippet_findings("blocking", base_files, BLOCKING_SNIPPET)
    msgs = " ".join(f.message for f in found)
    assert "time.sleep" in msgs and "open" in msgs and ".result()" in msgs
    marked = BLOCKING_SNIPPET.replace(
        "time.sleep(0.1)",
        "time.sleep(0.1)  # vmqlint: allow(blocking): fixture")
    found2 = snippet_findings("blocking", base_files, marked)
    assert not any("time.sleep" in f.message for f in found2)


def test_blocking_legacy_marker_still_honored(base_files):
    marked = BLOCKING_SNIPPET.replace(
        "time.sleep(0.1)",
        "time.sleep(0.1)  # lint: allow-blocking — deliberate")
    found = snippet_findings("blocking", base_files, marked)
    assert not any("time.sleep" in f.message for f in found)


def test_blocking_scans_tools(base_files):
    """The scan roots include ``tools/`` (the old lint hardcoded
    vernemq_tpu/) — a seeded defect there is caught, and the marker
    suppresses it there as it does in the product tree."""
    rel = "tools/_vmqlint_fixture.py"
    found, _ = core.run(passes=["blocking"], files=base_files,
                        overrides={rel: BLOCKING_SNIPPET}, paths=[rel])
    assert any(f.rel == rel and "time.sleep" in f.message for f in found)
    marked = BLOCKING_SNIPPET.replace(
        "time.sleep(0.1)",
        "time.sleep(0.1)  # vmqlint: allow(blocking): fixture")
    found = run_pass("blocking", base_files, overrides={rel: marked},
                     paths=[rel])
    assert not any("time.sleep" in f.message for f in found)
    assert any(f.rel == rel and "open" in f.message for f in found)


def test_shared_scan_roots_reach_chip_smoke(base_files):
    """ONE tuple of scan roots (``core.SCAN_ROOTS``) serves the file
    collection and both whole-program passes, and ``chip_smoke.py`` is
    in it: a defect seeded into the real file is caught by each."""
    from tools.vmqlint.passes import blocking, events_registry

    assert "chip_smoke.py" in core.SCAN_ROOTS
    assert blocking.BlockingPass.roots is core.SCAN_ROOTS
    assert events_registry.EventsRegistryPass.roots is core.SCAN_ROOTS
    rel = "chip_smoke.py"
    assert run_pass("blocking", base_files, paths=[rel]) == []
    seeded = base_files[rel].text + BLOCKING_SNIPPET
    found = run_pass("blocking", base_files, overrides={rel: seeded},
                     paths=[rel])
    assert any(f.rel == rel and "time.sleep" in f.message for f in found)
    seeded = base_files[rel].text + (
        "\nfrom vernemq_tpu.observability import events\n"
        "events.emit('no_such_event_code_xyz')\n")
    found = run_pass("events-registry", base_files,
                     overrides={rel: seeded})
    assert any(f.rel == rel and "no_such_event_code_xyz" in f.message
               for f in found)


# ---------------------------------------------------------- metrics corpus

def test_metrics_catches_bad_family_and_legacy_marker(base_files):
    src = 'def f(m):\n    m.observe("no_such_family_xyz", 1.0)\n'
    found = snippet_findings("metrics", base_files, src,
                             paths_only=False)
    assert any("no_such_family_xyz" in f.message for f in found)
    marked = src.replace("1.0)", "1.0)  # lint: observe-passthrough")
    assert snippet_findings("metrics", base_files, marked,
                            paths_only=False) == []


def test_metrics_real_passthrough_marker_flip(base_files):
    """The two real delegation seams carry the legacy marker; stripping
    either flips the tree red."""
    for rel in ("vernemq_tpu/observability/histogram.py",
                "vernemq_tpu/broker/metrics.py"):
        stripped = base_files[rel].text.replace(
            "# lint: observe-passthrough", "")
        found = run_pass("metrics", base_files,
                         overrides={rel: stripped})
        assert any(f.rel == rel for f in found), rel


def test_metrics_empty_help_caught(base_files):
    rel = "vernemq_tpu/broker/metrics.py"
    text = base_files[rel].text
    m = re.search(r'\("mqtt_connect_received",\s*\n?\s*"[^"]+"',
                  text)
    assert m, "counter table shape changed"
    mutated = text.replace(m.group(0),
                           '("mqtt_connect_received", ""', 1)
    found = run_pass("metrics", base_files, overrides={rel: mutated})
    assert any("empty HELP" in f.message for f in found)


# ----------------------------------------------------- knob-registry corpus

def test_knob_registry_phantom_read(base_files):
    src = ('class X:\n'
           '    def f(self):\n'
           '        cfg = self.broker.config\n'
           '        return cfg.get("tpu_breker_enabled", True)\n')
    found = snippet_findings("knob-registry", base_files, src,
                             paths_only=False)
    assert any("tpu_breker_enabled" in f.message for f in found)


def test_knob_registry_dict_params_not_confused(base_files):
    """A plain dict named cfg (the bridge/connector per-entry configs)
    is NOT config-shaped — no false positives on its keys."""
    src = ('def add_bridge(cfg):\n'
           '    return cfg.get("host", "127.0.0.1")\n')
    assert snippet_findings("knob-registry", base_files, src,
                            paths_only=False) == []


def test_knob_registry_dead_knob(base_files):
    rel = "vernemq_tpu/broker/config.py"
    text = base_files[rel].text
    mutated = text.replace(
        '"allow_anonymous": False,',
        '"allow_anonymous": False,\n    "vmqlint_dead_knob": 7,', 1)
    found = run_pass("knob-registry", base_files,
                     overrides={rel: mutated})
    assert any("vmqlint_dead_knob" in f.message
               and "never read" in f.message for f in found)


def test_knob_registry_dangling_alias(base_files):
    rel = "vernemq_tpu/broker/schema.py"
    text = base_files[rel].text
    mutated = text.replace(
        '"message_size_limit": "max_message_size",',
        '"message_size_limit": "max_message_size_typo",', 1)
    found = run_pass("knob-registry", base_files,
                     overrides={rel: mutated})
    assert any("max_message_size_typo" in f.message for f in found)


def test_knob_registry_alias_comprehension_targets_checked(base_files):
    """The {f"overload.{...}": k for k in (...)} families resolve: a
    typo inside the tuple is caught."""
    rel = "vernemq_tpu/broker/schema.py"
    text = base_files[rel].text
    mutated = text.replace('"overload_mode",', '"overload_modee",', 1)
    found = run_pass("knob-registry", base_files,
                     overrides={rel: mutated})
    assert any("overload_modee" in f.message for f in found)


# ---------------------------------------------------- fault-registry corpus

def test_fault_registry_unknown_point(base_files):
    src = ('from vernemq_tpu.robustness import faults\n'
           'def f():\n'
           '    faults.inject("device.dipatch")\n')
    found = snippet_findings("fault-registry", base_files, src,
                             paths_only=False)
    assert any("device.dipatch" in f.message for f in found)


def test_fault_registry_dead_registry_entry(base_files):
    rel = "vernemq_tpu/robustness/faults.py"
    text = base_files[rel].text
    mutated = text.replace(
        '"listener.bind":',
        '"listener.unbind":\n        "a point with no site",\n'
        '    "listener.bind":', 1)
    found = run_pass("fault-registry", base_files,
                     overrides={rel: mutated})
    assert any("listener.unbind" in f.message
               and "no faults.inject" in f.message for f in found)


def test_fault_registry_covers_batch_encode_site(base_files):
    """The batched fanout encoder's ``wire.encode`` seam is visible to
    the pass, not just grandfathered by the older per-frame site: strip
    every ``wire.encode`` inject from fastpath.py and the registry
    entry goes dead; strip only the per-frame site and the batch
    entry point alone keeps the registry satisfied."""
    rel = "vernemq_tpu/protocol/fastpath.py"
    text = base_files[rel].text
    site = 'faults.inject("wire.encode", max_delay_s=1.0)'
    # publish_header + publish_headers_batch each carry the seam
    assert text.count(site) == 2
    found = run_pass("fault-registry", base_files,
                     overrides={rel: text.replace(site, "pass")})
    assert any("'wire.encode'" in f.message
               and "no faults.inject" in f.message for f in found)
    # first occurrence is the per-frame publish_header site; with it
    # gone, the batch-encode site must satisfy the registry by itself
    found = run_pass("fault-registry", base_files,
                     overrides={rel: text.replace(site, "pass", 1)})
    assert not any("wire.encode" in f.message for f in found), \
        [f.render() for f in found]


def test_fault_registry_breaker_path_drift(base_files):
    src = ('def rows(mp):\n'
           '    return [{"path": "acl", "mountpoint": mp,\n'
           '             "state": "closed"}]\n')
    found = snippet_findings("fault-registry", base_files, src,
                             paths_only=False)
    assert any("'acl'" in f.message for f in found)
    # a dict with a "path" key but no "mountpoint" is NOT a breaker
    # admin row (file paths, HTTP routes) — no false positive
    other = ('ROW = {"path": "journal.log", "size": 1}\n')
    assert snippet_findings("fault-registry", base_files, other,
                            paths_only=False) == []
    # the selector idiom (None member) is checked; URL-path membership
    # tests are not
    sel = ('def f(path):\n'
           '    if path in (None, "retaned"):\n'
           '        return 1\n'
           '    if path in ("/status", "/health"):\n'
           '        return 2\n')
    found = snippet_findings("fault-registry", base_files, sel,
                             paths_only=False)
    assert any("retaned" in f.message for f in found)
    assert not any("/status" in f.message for f in found)


def test_fault_registry_runtime_validation():
    """The same registry gates `vmq-admin fault inject` at runtime."""
    from vernemq_tpu.admin.commands import CommandError, _fault_inject
    from vernemq_tpu.robustness import faults

    faults.validate_point("device.dispatch")
    faults.validate_point("device.*")  # glob matching >=1 point
    with pytest.raises(ValueError):
        faults.validate_point("device.dipatch")
    with pytest.raises(CommandError):
        _fault_inject(None, {"point": "device.dipatch"})
    assert faults.active() is None  # the failed inject installed no plan


# --------------------------------------------------- events-registry corpus

def test_events_registry_unknown_code(base_files):
    src = ('from vernemq_tpu.observability import events\n'
           'def f():\n'
           '    events.emit("braeker_open", detail="x")\n')
    found = snippet_findings("events-registry", base_files, src,
                             paths_only=False)
    assert any("braeker_open" in f.message
               and "KNOWN_EVENTS" in f.message for f in found)


def test_events_registry_non_literal_code_flagged(base_files):
    src = ('from vernemq_tpu.observability import events\n'
           'def f(code):\n'
           '    events.emit(code)\n')
    found = snippet_findings("events-registry", base_files, src,
                             paths_only=False)
    assert any("not a string literal" in f.message for f in found)


def test_events_registry_bare_emit_not_matched(base_files):
    """`emit` is a common name (the filter engine's aggregate hook is
    literally `self.filter_engine.emit`) — only `events.emit` /
    `_events.emit` receivers are journal sites."""
    src = ('class Engine:\n'
           '    def emit(self, what):\n'
           '        pass\n'
           'def f(eng):\n'
           '    eng.emit("not_an_event_code")\n'
           '    eng.inner.emit("also_not")\n')
    assert snippet_findings("events-registry", base_files, src,
                            paths_only=False) == []


def test_events_registry_dead_registry_entry(base_files):
    """A KNOWN_EVENTS entry with no events.emit site is a documented
    black-box signal that can never appear — flagged at the registry
    line."""
    rel = "vernemq_tpu/observability/events.py"
    text = base_files[rel].text
    needle = '    "breaker_open": ('
    assert needle in text
    mutated = text.replace(
        needle,
        '    "phantom_event": (\n'
        '        "nowhere",\n'
        '        "An event no site ever emits."),\n' + needle, 1)
    found = run_pass("events-registry", base_files,
                     overrides={rel: mutated})
    assert any("phantom_event" in f.message
               and "no events.emit" in f.message for f in found)


def test_events_registry_runtime_validation():
    """The same registry gates emit() at runtime: an unregistered
    code raises instead of journaling garbage."""
    from vernemq_tpu.observability import events

    with pytest.raises(KeyError):
        events.journal().emit("not_a_registered_code")


def test_events_registry_guards_handoff_codes(base_files):
    """The handoff FSM's journal codes are held to the same discipline:
    deleting the lone `handoff_fence` emit site leaves a dead registry
    entry the pass must flag (and the clean tree proves every handoff
    code currently has a live site)."""
    rel = "vernemq_tpu/cluster/handoff.py"
    text = base_files[rel].text
    assert 'events.emit("handoff_fence"' in text
    mutated = text.replace('events.emit("handoff_fence"',
                           'log.debug("handoff_fence"', 1)
    found = run_pass("events-registry", base_files,
                     overrides={rel: mutated})
    assert any("handoff_fence" in f.message
               and "no events.emit" in f.message for f in found)
    # unmutated tree: no handoff finding (all four codes live)
    clean = run_pass("events-registry", base_files)
    assert not any("handoff" in f.message for f in clean)


# ------------------------------------------------- framework / CLI surface

def test_marker_hygiene(base_files):
    src = ('import time\n'
           'async def f():\n'
           '    time.sleep(1)  # vmqlint: allow(blocking)\n'
           '    time.sleep(2)  # vmqlint: allow(blocing): typo pass\n')
    findings, _ = core.run(passes=["blocking"], files=base_files,
                           overrides={SNIP: src}, paths=[SNIP])
    mine = [f for f in findings if f.rel == SNIP]
    # no-reason marker still suppresses but is flagged itself;
    # unknown-pass marker suppresses nothing
    assert any(f.pass_name == "allow-marker" and "no reason"
               in f.message for f in mine)
    assert any(f.pass_name == "allow-marker" and "blocing"
               in f.message for f in mine)
    assert any(f.pass_name == "blocking" and f.line == 4
               for f in mine)


def test_star_marker_cannot_self_suppress_hygiene(base_files):
    """`# vmqlint: allow(*)` with no reason suppresses the defect on
    its line (that is its job) but the mandatory-reason finding it
    triggers is NOT suppressible by the marker it polices."""
    src = ('import time\n'
           'async def f():\n'
           '    time.sleep(1)  # vmqlint: allow(*)\n')
    findings, _ = core.run(passes=["blocking"], files=base_files,
                           overrides={SNIP: src}, paths=[SNIP])
    mine = [f for f in findings if f.rel == SNIP]
    assert not any(f.pass_name == "blocking" for f in mine)
    assert any(f.pass_name == "allow-marker" and "no reason"
               in f.message for f in mine)


def test_changed_scope_git_failure_scans_everything(base_files,
                                                    monkeypatch,
                                                    tmp_path):
    """A failing git probe must WIDEN --changed to the full tree, not
    narrow it to zero files (a vacuously green gate)."""
    assert core.changed_files(str(tmp_path)) is None  # not a git repo
    monkeypatch.setattr(core, "changed_files", lambda root: None)
    findings, stats = core.run(passes=["blocking"], files=base_files,
                               overrides={SNIP: BLOCKING_SNIPPET},
                               changed=True)
    assert stats["restricted_to"] is None
    assert any(f.rel == SNIP for f in findings)


def test_lock_discipline_sees_with_item_context_exprs(base_files):
    """`with open(...)` — the idiomatic sync-IO spelling — is flagged
    under a lock, both as a nested with and as a later item of the
    same with statement."""
    src = ('import threading\n'
           'class S:\n'
           '    def __init__(self):\n'
           '        self._lock = threading.Lock()\n'
           '    def a(self, p):\n'
           '        with self._lock:\n'
           '            with open(p) as fh:\n'
           '                return fh.read()\n'
           '    def b(self, p):\n'
           '        with self._lock, open(p) as fh:\n'
           '            return fh.read()\n'
           '    def c(self, p):\n'
           '        with open(p) as fh:  # lock not yet held: clean\n'
           '            return fh.read()\n')
    found = snippet_findings("lock-discipline", base_files, src)
    assert sorted(f.line for f in found
                  if "open" in f.message) == [7, 10]


def test_suppression_via_comment_block_above(base_files):
    src = ('import time\n'
           'async def f():\n'
           '    # vmqlint: allow(blocking): long reason that wraps\n'
           '    # over several comment lines before the statement\n'
           '    time.sleep(1)\n')
    assert snippet_findings("blocking", base_files, src) == []


def test_syntax_error_is_a_finding(base_files):
    findings, _ = core.run(passes=["blocking"], files=base_files,
                           overrides={SNIP: "def broken(:\n"},
                           paths=[SNIP])
    assert any(f.pass_name == "parse" and f.rel == SNIP
               for f in findings)


def test_suppression_survives_blank_line_after_comment(base_files):
    src = ('import time\n'
           'async def f():\n'
           '    # vmqlint: allow(blocking): deliberate stall\n'
           '\n'
           '    time.sleep(1)\n')
    assert snippet_findings("blocking", base_files, src) == []


def test_exit_code_contract(base_files, capsys):
    assert core.main(["--list"]) == 0
    assert core.main(["--pass", "nonexistent"]) == 2
    # a typo'd explicit path must error, not scan nothing and pass
    assert core.main(["vernemq_tpu/broker/sesion.py"]) == 2
    capsys.readouterr()


def test_json_output(capsys):
    rc = core.main(["--json", "--pass", "fault-registry"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out)
    assert doc["findings"] == []
    assert doc["passes"] == ["fault-registry"]
    assert doc["files_scanned"] > 100


def test_changed_scope_smoke(capsys):
    assert core.main(["--changed", "--pass", "blocking"]) == 0
    capsys.readouterr()


@pytest.mark.parametrize("argv", [
    [sys.executable, "tools/lint_blocking.py"],
    [sys.executable, "tools/lint_metrics.py"],
    [sys.executable, "-m", "tools.vmqlint"],
])
def test_shim_and_module_entrypoints(argv):
    """The legacy entry points stay runnable (exit 0 on the clean
    tree), as does the canonical module form run_tier1.sh uses."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(argv, cwd=ROOT, capture_output=True,
                         text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "clean" in res.stdout
