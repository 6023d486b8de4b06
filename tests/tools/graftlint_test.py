"""graftlint fixture suite: one minimal positive and one minimal
negative snippet per JGL rule, suppression-comment behavior, and a
tree-clean guard that keeps ``make lint`` green by construction.

The snippets are the rules' contract: if a rule's heuristic is tuned,
these pin what must still fire and what must stay quiet.
"""

from __future__ import annotations

from pathlib import Path

import pytest

# tools.graftlint resolves via pythonpath = ["src", "."] in pyproject.
from tools.graftlint import (
    RULES,
    run_paths,
    run_project_sources,
    run_source,
)
from tools.graftlint.cli import main as cli_main

REPO = Path(__file__).resolve().parent.parent.parent

# -- per-rule fixtures -----------------------------------------------------
# fmt: off
POSITIVE = {
    "JGL001": '''
import jax
import numpy as np

@jax.jit
def step(state, batch):
    return state + np.asarray(batch)
''',
    "JGL002": '''
import jax

@jax.jit
def fold(events):
    total = 0
    for e in events:
        total += e
    return total
''',
    "JGL003": '''
import jax

class HistogramState:
    pass

def _step_impl(state, flat):
    return HistogramState()

class Hist:
    def __init__(self):
        self._step = jax.jit(_step_impl)
''',
    "JGL004": '''
import threading

class Counter:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def on_message(self):
        self.count += 1

    def snapshot(self):
        return self.count
''',
    "JGL005": '''
import time

async def pump():
    time.sleep(0.1)
''',
    "JGL006": '''
import jax.numpy as jnp

class Hist:
    def step(self, state):
        return self._step(state, jnp.asarray(1.0, self._dtype))
''',
    "JGL007": '''
def process(msgs):
    for m in msgs:
        try:
            decode(m)
        except Exception:
            pass
''',
    "JGL008": '''
import jax
from functools import partial

@jax.jit
def step(state, bins):
    return state

stepper = partial(step, bins=[0.0, 1.0])
''',
    "JGL009": '''
import jax

def fan_out(jobs, batch, states):
    for job in jobs:
        states[job] = step(states[job], jax.device_put(batch))
''',
    "JGL015": '''
import jax
import numpy as np

def publish_all(jobs, batch):
    out = {}
    for job in jobs:
        out[job] = jax.device_get(job.state)
    for job in jobs:
        job.state.block_until_ready()
    for rec in jobs:
        summary = rec.hist.finalize(rec.state)
        out[rec] = np.asarray(summary)
    return out
''',
    "JGL010": '''
import queue
import threading

class Pipeline:
    def __init__(self):
        self._q = queue.Queue()

    def worker(self):
        while True:
            item = self._q.get()
            step(item)
''',
    # Whole-program: A takes A._lock then B._lock (via call), B takes
    # B._lock then A._lock — a cycle in the lock-order graph.
    "JGL011": '''
import threading

class Batcher:
    def __init__(self):
        self._lock = threading.Lock()
        self._pipe = Pipeline()

    def flush(self):
        with self._lock:
            self._pipe.submit()

class Pipeline:
    def __init__(self):
        self._lock = threading.Lock()
        self._batcher = Batcher()

    def submit(self):
        with self._lock:
            pass

    def drain(self):
        with self._lock:
            self._batcher.flush()
''',
    # A worker thread and the main thread both write self.count, no lock.
    "JGL012": '''
import threading

class Svc:
    def __init__(self):
        self.count = 0
        self._worker = threading.Thread(target=self._run)

    def _run(self):
        self.count = self.count + 1

    def poll(self):
        self.count = 0
''',
    # A mutable staged batch crosses a queue hand-off undetached.
    "JGL013": '''
import queue
import threading

class Stage:
    def __init__(self):
        self._q = queue.Queue(maxsize=2)

    def feed(self, batch: EventBatch):
        self._q.put(batch, timeout=0.1)
''',
    # The jitted step reads _scale; no key tuple mentions it.
    "JGL014": '''
import jax

class Hist:
    def __init__(self, bins, scale):
        self._bins = bins
        self._scale = scale
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))

    @property
    def fuse_key(self):
        return ("fuse", self._bins)

    def _step_impl(self, state, flat):
        return state * self._scale
''',
    # The donated state is read (and re-dispatched) after the dispatch
    # consumed its buffers.
    "JGL016": '''
import numpy as np

def tick_once(hist, state, staged):
    new_state = hist.step_many((state,), staged)
    total = np.sum(state.window)
    state = hist.step_flat(state, staged)
    return new_state, total
''',
    # Mesh-scoped code (jax.sharding import): a placement-less
    # device_put commits to the default device, and the per-job loop
    # feeds it to a mesh-sharded dispatch — one implicit reshard per
    # job (both shapes of the hazard in one fixture).
    "JGL017": '''
import jax
from jax.sharding import NamedSharding

def serve(jobs, sharded_hist, batch):
    for job in jobs:
        staged = jax.device_put(batch)
        job.state = sharded_hist.step(job.state, staged, staged)
''',
    # Both shapes: a host clock read and a registry increment inside a
    # traced body — each fires once per TRACE, not per execution.
    "JGL018": '''
import time
import jax

from esslivedata_tpu.telemetry import REGISTRY

STEPS = REGISTRY.counter("steps_total", "steps")

@jax.jit
def step(state, batch):
    t0 = time.perf_counter()
    state = state + batch
    STEPS.inc()
    return state, time.perf_counter() - t0
''',
    # Both shapes of the broadcast fan-out hazard: the accept thread
    # mutates the subscriber registry without the lock the publish
    # thread's iteration holds, and per-tick frames append to a list
    # nothing ever drains or bounds.
    "JGL019": '''
import threading

class Hub:
    def __init__(self):
        self._lock = threading.Lock()
        self._subscribers = {}
        self._frames = []

    def subscribe(self, sub_id, sub):
        self._subscribers[sub_id] = sub

    def publish(self, frame):
        self._frames.append(frame)
        with self._lock:
            for sub in self._subscribers.values():
                sub.send(frame)
''',
    # Both shapes of the persistence hazard, in a module the atomic
    # writer already marks as persistence-scoped: a second writer that
    # skips the discipline entirely (direct final-path write), and one
    # that renames but never fsyncs.
    "JGL020": '''
import os
import numpy as np

def save_manifest(path, blob):
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)

def save_state(path, arr):
    with open(path, "wb") as f:
        np.save(f, arr)

def save_marker(path, blob):
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
''',
    # A traced intermediate stored into self under trace: the classic
    # leaked tracer.
    "JGL021": '''
import jax
import jax.numpy as jnp

class Hist:
    @jax.jit
    def step(self, state, batch):
        total = jnp.sum(batch)
        self.last_total = total
        return state + total
''',
    # A containment reset whose exit path skips the epoch protocol
    # (the file is a protocol participant: another method notes).
    "JGL022": '''
class Manager:
    def recover(self, members):
        for rec, offer in members:
            if offer.state_lost:
                offer.reset()
                rec.warning = "accumulation reset"

    def adopt(self, rec):
        rec.job.note_state_lost()
''',
    # A checkpoint fsync reached while the plane lock is held — two
    # frames down, through the atomic-write helper.
    "JGL023": '''
import os
import threading

class Plane:
    def __init__(self):
        self._lock = threading.Lock()

    def checkpoint(self, f):
        with self._lock:
            self._dump(f)

    def _dump(self, f):
        os.fsync(f.fileno())
''',
    # A suppression for a rule that no longer fires on that line.
    "JGL024": '''
def healthy():
    return 1  # graftlint: disable=JGL007 vestigial after refactor
''',
    # Both shapes of the cardinality leak: a job-id label bound on a
    # direct counter child, and a per-subscriber gauge series.
    "JGL025": '''
from esslivedata_tpu.telemetry import REGISTRY

FRAMES = REGISTRY.counter("frames_total", "frames", labelnames=("job",))
DEPTH = REGISTRY.gauge("depth", "queue depth", labelnames=("subscriber",))

def publish(result, sub):
    FRAMES.labels(job=f"{result.job_id}").inc()
    DEPTH.set(sub.depth(), subscriber=str(sub.sub_id))
''',
    # A reconnect loop that redials on a fixed interval: no bound, no
    # jitter — the lockstep-stampede shape JGL026 exists for.
    "JGL026": '''
import http.client
import time

def consume(host, on_line):
    while True:
        try:
            conn = http.client.HTTPConnection(host)
            conn.connect()
            for line in conn.getresponse():
                on_line(line)
        except OSError:
            time.sleep(1.0)
            continue
''',
    # A digest-keyed class whose message handlers replace the LUT —
    # by rebind AND by in-place slice store (the sneakier form: the
    # object identity survives, so even identity-keyed caches rot):
    # every staging/tick/static cache keyed on the old digest keeps
    # serving stale results — the ADR 0110/0113 bypass JGL027 exists
    # for. Both shapes must fire.
    "JGL027": '''
class Hist:
    def __init__(self):
        self._lut = None
        self._digest = "a"

    @property
    def layout_digest(self):
        return self._digest

    def on_geometry_message(self, lut):
        self._lut = lut

    def on_refill(self, lut):
        self._lut[:] = lut
''',
    # In scope via the wire import; copies the payload and accumulates
    # a fresh ndarray per message inside the consume loop.
    "JGL028": '''
import numpy as np
from esslivedata_tpu.kafka import wire

def consume(raws):
    chunks = []
    for raw in raws:
        buf = bytes(raw.value())
        msg = wire.decode_ev44(buf)
        chunks.append(np.asarray(msg.time_of_flight))
    return np.concatenate(chunks)
''',
}

NEGATIVE = {
    # np on a non-traced (construction-time) value outside the jit region.
    "JGL001": '''
import jax
import jax.numpy as jnp
import numpy as np

class Hist:
    def __init__(self, edges):
        self._edges = np.asarray(edges)
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))

    def _step_impl(self, state, batch):
        return state + jnp.sum(batch)
''',
    # Loop over a static literal unrolls a known, fixed amount.
    "JGL002": '''
import jax

@jax.jit
def fold(state):
    for axis in (0, 1):
        state = state.sum(axis=0)
    return state
''',
    # Donated update and a non-donated read-only views program.
    "JGL003": '''
import jax

class HistogramState:
    pass

class Hist:
    def __init__(self):
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))
        self._views = jax.jit(self._views_impl)

    def _step_impl(self, state, flat):
        return HistogramState()

    def _views_impl(self, state):
        return (state, state)
''',
    # The same read-modify-write, but under the lock.
    "JGL004": '''
import threading

class Counter:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def on_message(self):
        with self._lock:
            self.count += 1

    def snapshot(self):
        with self._lock:
            return self.count
''',
    "JGL005": '''
import asyncio

async def pump():
    await asyncio.sleep(0.1)
''',
    # Constant staged once at construction, not per step.
    "JGL006": '''
import jax.numpy as jnp

class Hist:
    def __init__(self):
        self._one = jnp.asarray(1.0)

    def step(self, state):
        return self._step(state, self._one)
''',
    # Narrow type + logged broad handler are both fine.
    "JGL007": '''
import logging

logger = logging.getLogger(__name__)

def process(msgs):
    for m in msgs:
        try:
            decode(m)
        except ValueError:
            pass
        except Exception:
            logger.warning("poison message", exc_info=True)
''',
    # Hashable (tuple) static arg, and mutable partial of a plain function.
    "JGL008": '''
import jax
from functools import partial

@jax.jit
def step(state, bins):
    return state

stepper = partial(step, bins=(0.0, 1.0))

def host_helper(xs):
    return xs

helper = partial(host_helper, [1, 2])
''',
    # Fetch hoisted below the loop (one packed device_get), fetches in
    # non-job loops, and np.asarray of host values all stay quiet.
    "JGL015": '''
import jax
import numpy as np

def publish_all(jobs, batches, precomputed):
    packed = pack(jobs)
    flat = jax.device_get(packed)
    for job in jobs:
        out = np.asarray(job.host_counts)
    for batch in batches:
        fetched = jax.device_get(batch)
    # 'rec' must match whole tokens only: 'precomputed'/'recent' are
    # not per-job loops.
    for arr in precomputed:
        recent = jax.device_get(arr)
    return flat, out, fetched, recent
''',
    # Staging hoisted above the loop, per-iteration values staged inside
    # it, values derived from the loop variable, and nested-loop /
    # comprehension targets all stay quiet.
    "JGL009": '''
import jax

def fan_out(jobs, batches, state):
    staged = jax.device_put(batches[0])
    for b in batches:
        state = step(state, jax.device_put(b))
    for i in range(4):
        x = batches[i]
        state = step(state, jax.device_put(x))
    for job in jobs:
        for b in batches:
            state = step(state, jax.device_put(b))
    for job in jobs:
        parts = [jax.device_put(b) for b in batches]
    return step(state, staged)
''',
    # Bounded construction, timeboxed blocking ops, and the nonblocking
    # forms all stay quiet; so does a Queue in a module without threads.
    "JGL010": '''
import queue
import threading

class Pipeline:
    def __init__(self, depth):
        self._q = queue.Queue(maxsize=depth)

    def submit(self, item):
        self._q.put(item, timeout=0.1)

    def try_submit(self, item):
        self._q.put_nowait(item)

    def worker(self):
        while True:
            try:
                item = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            step(item)

    def drain_one(self):
        return self._q.get(False)

    def positional_forms(self, item):
        self._q.put(item, True, 0.1)
        return self._q.get(True, 0.1)
''',
    # Same two classes, one global order: A._lock -> B._lock only.
    "JGL011": '''
import threading

class Batcher:
    def __init__(self):
        self._lock = threading.Lock()
        self._pipe = Pipeline()

    def flush(self):
        with self._lock:
            self._pipe.submit()

class Pipeline:
    def __init__(self):
        self._lock = threading.Lock()
        self._batcher = Batcher()

    def submit(self):
        with self._lock:
            pass

    def drain(self):
        self._batcher.flush()
''',
    # Both roles write under the one shared lock; __init__ is exempt.
    "JGL012": '''
import threading

class Svc:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        self._worker = threading.Thread(target=self._run)

    def _run(self):
        with self._lock:
            self.count = self.count + 1

    def poll(self):
        with self._lock:
            self.count = 0
''',
    # Detached before the hand-off (directly and via rebinding).
    "JGL013": '''
import queue
import threading

class Stage:
    def __init__(self):
        self._q = queue.Queue(maxsize=2)

    def feed(self, batch: EventBatch):
        self._q.put(batch.detach(), timeout=0.1)

    def feed_rebound(self, batch: EventBatch):
        owned = batch.detach()
        self._q.put(owned, timeout=0.1)
''',
    # Every traced read is keyed, derived-declared, or a class constant.
    "JGL014": '''
import jax

class Hist:
    _FLOOR = 1e-12

    def __init__(self, bins, scale):
        self._bins = bins
        # graft: key-derived=_scale recomputed from bins on rebuild
        self._scale = scale
        self._n = len(bins)
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))

    @property
    def fuse_key(self):
        return ("fuse", self._bins, self._n)

    def _step_impl(self, state, flat):
        return state * self._scale * self._FLOOR
''',
    # Rebinding the handle from the dispatch's return clears the taint;
    # the except handler may probe consumed-ness and rebuild; a fresh
    # loop iteration rebinds before it re-dispatches.
    "JGL016": '''
def tick_loop(hist, jobs, staged):
    for job in jobs:
        state = job.get_state()
        try:
            state = hist.step_many((state,), staged)
        except RuntimeError:
            if state_consumed(state):
                state = hist.init_state()
        job.set_state(state)
''',
    # Explicitly placed: one hop onto the event NamedSharding before
    # the loop (stage_for idiom) — no implicit reshard anywhere. The
    # single-arg device_put lives in a NON-mesh-scoped helper in real
    # code (ops/event_batch.dispatch_safe); here everything is placed.
    "JGL017": '''
import jax
from jax.sharding import NamedSharding, PartitionSpec

def serve(jobs, sharded_hist, batch, mesh):
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    staged = jax.device_put(batch, sharding)
    for job in jobs:
        job.state = sharded_hist.step(job.state, staged, staged)
''',
    # The worked pattern: the traced body stays pure; timing and the
    # registry record happen on the host side, around the dispatch.
    "JGL018": '''
import time
import jax

from esslivedata_tpu.telemetry import REGISTRY

STEPS = REGISTRY.counter("steps_total", "steps")

@jax.jit
def _step_impl(state, batch):
    return state + batch

def step(state, batch):
    t0 = time.perf_counter()
    out = _step_impl(state, batch)
    STEPS.inc()
    return out, time.perf_counter() - t0
''',
    # The worked broadcast pattern: registry mutations under the lock
    # (a *_locked helper trusted at its call site), the per-subscriber
    # hand-off a bounded queue, and the only growable list drained by a
    # method that reassigns it.
    "JGL019": '''
import queue
import threading

class Hub:
    def __init__(self):
        self._lock = threading.Lock()
        self._subscribers = {}
        self._pending_frames = []
        self._queue = queue.Queue(maxsize=8)

    def subscribe(self, sub_id, sub):
        with self._lock:
            self._sweep_locked()
            self._subscribers[sub_id] = sub

    def _sweep_locked(self):
        self._subscribers.pop("stale", None)

    def publish(self, frame):
        with self._lock:
            self._pending_frames.append(frame)
            for sub in self._subscribers.values():
                sub.send(frame)

    def drain(self):
        with self._lock:
            frames, self._pending_frames = self._pending_frames, []
        return frames
''',
    # The worked persistence pattern: every writer routes through one
    # atomic helper (tmp + fsync + replace); readers and in-memory
    # writes never fire; a tempfile scratch write in a NON-persistence
    # module (no rename/fsync anywhere, neutral filename) is out of
    # scope entirely.
    "JGL020": '''
import io
import os
import numpy as np

def atomic_write(path, payload):
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)

def save_state(path, arr):
    buf = io.BytesIO()
    np.save(buf, arr)
    atomic_write(path, buf.getvalue())

def load_state(path):
    with open(path, "rb") as f:
        return np.load(f)
''',
    # The worked jit-boundary pattern: traced values RETURN from the
    # traced body and land in host state outside it; a host constant
    # bound to self under trace (trace-time config capture) and a
    # traced value collected into a LOCAL list are both legal.
    "JGL021": '''
import jax
import jax.numpy as jnp

class Hist:
    @jax.jit
    def step(self, state, batch):
        self._traced_once = True
        parts = []
        for shard in range(4):
            parts.append(jnp.sum(batch))
        return state + sum(parts)

    def host_step(self, state, batch):
        out = self.step(state, batch)
        self.last_total = out
        return out
''',
    # The worked containment pattern: every failure-path reset reaches
    # the protocol — directly, through a noting helper, or via a
    # state_epoch bump; a reset on a non-failure path (plain restart)
    # is out of scope.
    "JGL022": '''
class Manager:
    def _recover(self, rec):
        rec.job.note_state_lost()

    def recover(self, members):
        for rec, offer in members:
            if offer.state_lost:
                offer.reset()
                self._recover(rec)

    def handle(self, rec, offer):
        try:
            publish()
        except Exception:
            if consumed(offer.args):
                offer.set_state(offer.hist.init_state())
                rec.job.state_epoch += 1

    def restart(self, offer):
        offer.reset()
''',
    # The worked critical-section pattern: snapshot under the lock,
    # block after releasing it; a blocking call inside a *_locked
    # helper is the caller's lock by convention and is judged at
    # lock-holding call sites only (none here).
    "JGL023": '''
import os
import threading

class Plane:
    def __init__(self):
        self._lock = threading.Lock()

    def checkpoint(self, f):
        with self._lock:
            entries = list(self._pending)
        serialize(entries)
        os.fsync(f.fileno())

    def _flush_locked(self, f):
        os.fsync(f.fileno())
''',
    # Both suppressions mask live findings: the line directive a real
    # JGL007, the file-wide one a real JGL006.
    "JGL024": '''
import jax.numpy as jnp
# graftlint: disable-file=JGL006 generated lookup tables

class Hist:
    def step(self, state):
        return self._step(state, jnp.asarray(1.0, self._dtype))

def process(msgs):
    for m in msgs:
        try:
            decode(m)
        except Exception:  # graftlint: disable=JGL007 poison drop is counted upstream
            pass
''',
    # The worked cardinality pattern: bounded literal/enum-style labels
    # on direct instruments, and the per-entity series exposed through
    # a keyed collector building Sample rows from live state.
    "JGL025": '''
from esslivedata_tpu.telemetry import REGISTRY, MetricFamily, Sample

FRAMES = REGISTRY.counter("frames_total", "frames", labelnames=("kind",))
LAT = REGISTRY.histogram("lat_seconds", "latency", labelnames=("stage",))

def publish(blob, stage):
    FRAMES.labels(kind="keyframe").inc(len(blob))
    LAT.observe(0.5, stage=stage)

class Hub:
    def __init__(self):
        self._subscribers = {}
        REGISTRY.register_collector("hub", self._telemetry)

    def _telemetry(self):
        fam = MetricFamily("hub_queue_depth", "gauge", "depths")
        for sub_id, sub in sorted(self._subscribers.items()):
            fam.samples.append(
                Sample("", (("subscriber", str(sub_id)),), sub.depth())
            )
        return [fam]
''',
    # The polite shape: bounded exponential backoff (min cap) with a
    # seeded jitter multiplier, reset on success — and the helper
    # variant (any *backoff* callee) is equally clean.
    "JGL026": '''
import http.client
import random
import time

def consume(host, stop, on_line):
    attempts = 0
    while not stop.is_set():
        try:
            conn = http.client.HTTPConnection(host)
            conn.connect()
            for line in conn.getresponse():
                on_line(line)
            attempts = 0
        except OSError:
            attempts += 1
            delay = min(10.0, 0.5 * (2 ** attempts))
            time.sleep(delay * (0.5 + random.random()))
''',
    # The sanctioned shape: the swap_* path replaces the table AND
    # re-fingerprints, so every key misses cleanly; the lazy device
    # materialization from the host twin is content-neutral.
    "JGL027": '''
class Hist:
    def __init__(self):
        self.lut_host = None
        self._lut_dev = None
        self._digest = "a"

    @property
    def layout_digest(self):
        return self._digest

    @property
    def lut(self):
        if self._lut_dev is None:
            self._lut_dev = list(self.lut_host)
        return self._lut_dev

    def swap_lut(self, lut):
        self.lut_host = lut
        self._lut_dev = None
        self._digest = None
''',
    # The batch decode shape: header views appended (no ndarray
    # allocation in the loop), one arena fill outside it. The single
    # upfront allocations (empty/zeros) sit outside the loop too.
    "JGL028": '''
import numpy as np
from esslivedata_tpu.kafka import wire

def consume(raws, arena):
    views = []
    errors = []
    for i, raw in enumerate(raws):
        try:
            views.append(wire.walk_ev44(raw.value()))
        except wire.WireError as err:
            errors.append((i, err))
    offsets = np.zeros(len(views) + 1, dtype=np.int64)
    for j, v in enumerate(views):
        offsets[j + 1] = offsets[j] + v.n_tof
    total = int(offsets[-1])
    pid = arena.pixel[:total]
    toa = arena.toa[:total]
    for j, v in enumerate(views):
        v.fill_into(pid[offsets[j]:offsets[j + 1]],
                    toa[offsets[j]:offsets[j + 1]])
    return pid, toa, offsets, errors
''',
}
# fmt: on


@pytest.mark.parametrize("rule_id", sorted(POSITIVE))
def test_positive_fires(rule_id):
    findings = run_source(POSITIVE[rule_id], path="pos.py")
    assert rule_id in {f.rule for f in findings}, (
        f"{rule_id} did not fire on its positive fixture: {findings}"
    )


@pytest.mark.parametrize("rule_id", sorted(NEGATIVE))
def test_negative_quiet(rule_id):
    findings = [
        f
        for f in run_source(NEGATIVE[rule_id], path="neg.py")
        if f.rule == rule_id
    ]
    assert not findings, f"{rule_id} false-positive: {findings}"


def test_every_rule_has_fixtures():
    # Trace-scope rules (JGL10x) fire on lowered programs and
    # protocol-scope rules (JGL20x) on explored state machines, not
    # source snippets — their seeded positive/negative fixtures live in
    # graftlint_trace_test.py and protocol_mutation_test.py.
    ast_rules = {
        r
        for r, rule in RULES.items()
        if rule.scope not in ("trace", "protocol")
    }
    assert set(POSITIVE) == ast_rules
    assert set(NEGATIVE) == ast_rules


def test_findings_carry_location_and_render():
    findings = run_source(POSITIVE["JGL007"], path="svc.py")
    f = next(f for f in findings if f.rule == "JGL007")
    assert f.path == "svc.py" and f.line > 0
    assert f.render().startswith("svc.py:")
    assert "JGL007" in f.render()


# -- suppressions ----------------------------------------------------------

def test_same_line_suppression():
    src = POSITIVE["JGL007"].replace(
        "except Exception:", "except Exception:  # graftlint: disable=JGL007"
    )
    assert not run_source(src)


def test_suppression_with_trailing_justification_prose():
    # The documented style puts the justification beside the disable;
    # prose after the id list must not break the match.
    src = POSITIVE["JGL007"].replace(
        "except Exception:",
        "except Exception:  # graftlint: disable=JGL007 best-effort wakeup",
    )
    assert not run_source(src)


def test_preceding_line_suppression():
    src = '''
try:
    x = 1
# graftlint: disable=JGL007
except Exception:
    pass
'''
    assert not run_source(src)


def test_file_level_suppression():
    src = "# graftlint: disable-file=JGL007\n" + POSITIVE["JGL007"]
    assert not run_source(src)


def test_suppression_is_rule_specific():
    # Suppressing an unrelated rule must not silence the finding.
    src = POSITIVE["JGL007"].replace(
        "except Exception:", "except Exception:  # graftlint: disable=JGL001"
    )
    assert any(f.rule == "JGL007" for f in run_source(src))


def test_disable_all_wildcard():
    src = "# graftlint: disable-file=all\n" + POSITIVE["JGL001"]
    assert not run_source(src)


def test_directive_inside_string_literal_has_no_effect():
    # Documentation ABOUT the directive (docstrings, string literals)
    # must not suppress anything — only real comment tokens count.
    src = '''
"""Intentional swallows carry a `# graftlint: disable-file=JGL007` marker."""

try:
    x = 1
except Exception:
    pass
'''
    assert any(f.rule == "JGL007" for f in run_source(src))


def test_null_byte_file_reported_not_crashing(tmp_path):
    bad = tmp_path / "nul.py"
    bad.write_bytes(b"x = 1\x00\n")
    good = tmp_path / "ok_hazard.py"
    good.write_text(POSITIVE["JGL007"])
    findings, errors = run_paths([str(tmp_path)])
    # The poisoned file lands in the error channel; the rest still lints.
    assert len(errors) == 1 and "nul.py" in errors[0]
    assert any(f.rule == "JGL007" for f in findings)


# -- engine plumbing -------------------------------------------------------

def test_select_filters_rules():
    both = POSITIVE["JGL007"] + "\nimport time\nasync def f():\n    time.sleep(1)\n"
    only = run_source(both, select=frozenset({"JGL005"}))
    assert {f.rule for f in only} == {"JGL005"}


def test_root_under_dotted_directory_is_still_linted(tmp_path):
    # The hidden-dir filter must apply below the given root only: a
    # checkout living under a dotted ancestor (CI caches, pre-commit
    # clones) must not silently lint nothing.
    root = tmp_path / ".cache" / "proj"
    root.mkdir(parents=True)
    (root / "dirty.py").write_text(POSITIVE["JGL007"])
    (root / ".venv").mkdir()
    (root / ".venv" / "vendored.py").write_text(POSITIVE["JGL007"])
    findings, errors = run_paths([str(root)])
    assert not errors
    assert [Path(f.path).name for f in findings] == ["dirty.py"]


def test_nonexistent_path_fails_the_gate(tmp_path):
    # A typo'd path in CI/Makefile must not become a green no-op.
    findings, errors = run_paths([str(tmp_path / "no_such_tree")])
    assert not findings
    assert len(errors) == 1 and "no such file" in errors[0]
    assert cli_main([str(tmp_path / "no_such_tree")]) == 1


def test_existing_non_python_path_fails_the_gate(tmp_path):
    # Same invariant for an existing-but-unlintable argument.
    readme = tmp_path / "README.md"
    readme.write_text("# not python\n")
    findings, errors = run_paths([str(readme)])
    assert not findings
    assert len(errors) == 1 and "not a directory or .py file" in errors[0]
    assert cli_main([str(readme)]) == 1


def test_syntax_error_reported_not_raised(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    findings, errors = run_paths([str(tmp_path)])
    assert not findings
    assert len(errors) == 1 and "bad.py" in errors[0]


def test_cli_exit_codes(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(POSITIVE["JGL007"])
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli_main([str(clean)]) == 0
    assert cli_main([str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "JGL007" in out and "dirty.py" in out


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in RULES:
        assert rule_id in out


def test_jit_closure_reaches_helpers():
    # A helper called from a jit-wrapped method is traced: host syncs
    # inside it must be flagged even though it carries no decorator.
    src = '''
import jax
import numpy as np

class H:
    def __init__(self):
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))

    def _step_impl(self, state, x):
        return self._helper(state, x)

    def _helper(self, state, x):
        return state + np.asarray(x)
'''
    assert any(f.rule == "JGL001" for f in run_source(src))


@pytest.mark.parametrize("method", ["span", "aggregate", "annotated"])
def test_jgl018_covers_every_timed_region_of_the_tracer(method):
    # The tracer's context managers all time (or annotate) a host
    # region: inside a traced body each would fire once per TRACE.
    src = f'''
import jax

from esslivedata_tpu.telemetry.trace import TRACER

@jax.jit
def step(state, batch):
    with TRACER.{method}("h2d_copy"):
        return state + batch
'''
    assert any(f.rule == "JGL018" for f in run_source(src))


# -- the acceptance gate ---------------------------------------------------

def test_src_tree_is_clean():
    """`python -m tools.graftlint src/esslivedata_tpu/` must stay at zero
    unsuppressed findings (the make-lint gate, ISSUE 1 acceptance)."""
    findings, errors = run_paths([str(REPO / "src" / "esslivedata_tpu")])
    assert not errors, errors
    assert not findings, "\n".join(f.render() for f in findings)


def test_tools_tree_is_clean():
    findings, errors = run_paths([str(REPO / "tools")])
    assert not errors, errors
    assert not findings, "\n".join(f.render() for f in findings)


# -- whole-program pass (JGL011-014, docs/adr/0112) ------------------------

# The regression fixture the tentpole demands: the real batcher/pipeline
# lock pair split across TWO modules, inverted. Modeled on a batcher
# with an RLock'd set_window and
# core/ingest_pipeline.py (Condition'd submit): if a completion callback
# ever called back into the batcher under the pipeline's state lock
# while the batcher submits under its own lock, these would deadlock.
_BATCHER_MOD = '''
import threading

class RateAwareMessageBatcher:
    def __init__(self):
        self._lock = threading.RLock()
        self._pipeline = None

    def attach(self, pipeline: IngestPipeline):
        self._pipeline = pipeline

    def set_window(self, window):
        with self._lock:
            self._pipeline.submit(window)
'''

_PIPELINE_MOD = '''
import threading

from batcher import RateAwareMessageBatcher

class IngestPipeline:
    def __init__(self, batcher: RateAwareMessageBatcher):
        self._state_lock = threading.Condition()
        self._batcher = batcher

    def submit(self, window):
        with self._state_lock:
            pass

    def on_complete(self, window):
        with self._state_lock:
            self._batcher.set_window(window)
'''


def test_lock_order_inversion_detected_across_two_modules():
    findings = run_project_sources(
        {"batcher.py": _BATCHER_MOD, "pipeline.py": _PIPELINE_MOD}
    )
    hits = [f for f in findings if f.rule == "JGL011"]
    # Both halves of the inversion report, each in its own module, each
    # naming the counter-site in the other file.
    assert {f.path for f in hits} == {"batcher.py", "pipeline.py"}
    assert any("pipeline.py" in f.message for f in hits if f.path == "batcher.py")


def test_consistent_cross_module_order_is_quiet():
    consistent = _PIPELINE_MOD.replace(
        """    def on_complete(self, window):
        with self._state_lock:
            self._batcher.set_window(window)""",
        """    def on_complete(self, window):
        self._batcher.set_window(window)""",
    )
    findings = run_project_sources(
        {"batcher.py": _BATCHER_MOD, "pipeline.py": consistent}
    )
    assert not [f for f in findings if f.rule == "JGL011"]


def test_thread_annotation_drives_role_inference():
    # The escape hatch: without the annotation the callback's role is
    # unknowable (it flows through a parameter) and JGL012 stays quiet;
    # with it, the cross-role unlocked write fires.
    template = '''
import threading

class Proc:
    def __init__(self):
        self._pending = None

    {annot}
    def on_complete(self, window):
        self._pending = window

    def apply(self):
        policy, self._pending = self._pending, None
'''
    quiet = run_source(template.format(annot="# unannotated"))
    assert not [f for f in quiet if f.rule == "JGL012"]
    loud = run_source(template.format(annot="# graft: thread=step"))
    assert [f for f in loud if f.rule == "JGL012"]


def test_jgl012_requires_common_lock_not_just_any_lock():
    src = '''
import threading

class Svc:
    def __init__(self):
        self.count = 0
        self._lock_a = threading.Lock()
        self._lock_b = threading.Lock()
        self._worker = threading.Thread(target=self._run)

    def _run(self):
        with self._lock_a:
            self.count = 1

    def poll(self):
        with self._lock_b:
            self.count = 0
'''
    findings = [f for f in run_source(src) if f.rule == "JGL012"]
    assert findings and "DIFFERENT locks" in findings[0].message


def test_jgl013_flags_forwarded_put_at_the_call_site():
    src = '''
import queue
import threading

class Stage:
    def __init__(self):
        self._q = queue.Queue(maxsize=2)

    def _put(self, q, item):
        q.put(item, timeout=0.1)

    def feed(self, batch: EventBatch):
        self._put(self._q, batch)

    def feed_safe(self, batch: EventBatch):
        self._put(self._q, batch.detach())
'''
    hits = [f for f in run_source(src) if f.rule == "JGL013"]
    assert len(hits) == 1 and hits[0].line == 13


def test_jgl014_key_derived_annotation_covers_attr():
    src = POSITIVE["JGL014"].replace(
        "self._scale = scale",
        "# graft: key-derived=_scale recomputed on every rebuild\n"
        "        self._scale = scale",
    )
    assert not [f for f in run_source(src) if f.rule == "JGL014"]


def test_project_findings_obey_line_suppressions():
    # JGL012 reports every unguarded site, so each write carries its
    # own suppression (which also keeps both live for JGL024).
    src = POSITIVE["JGL012"].replace(
        "self.count = self.count + 1",
        "self.count = self.count + 1  "
        "# graftlint: disable=JGL012 single-writer handshake",
    ).replace(
        "def poll(self):\n        self.count = 0",
        "def poll(self):\n        self.count = 0  "
        "# graftlint: disable=JGL012 single-writer handshake",
    )
    assert not [f for f in run_source(src) if f.rule == "JGL012"]


def test_jgl012_reports_every_unguarded_site():
    findings = [
        f for f in run_source(POSITIVE["JGL012"]) if f.rule == "JGL012"
    ]
    assert len(findings) == 2, findings
    assert {f.line for f in findings} == {10, 13}


def test_jobs_parallel_matches_serial(tmp_path):
    (tmp_path / "a.py").write_text(POSITIVE["JGL007"])
    (tmp_path / "b.py").write_text(POSITIVE["JGL012"])
    (tmp_path / "c.py").write_text(_BATCHER_MOD)
    serial = run_paths([str(tmp_path)], jobs=1)
    parallel = run_paths([str(tmp_path)], jobs=2)
    assert serial == parallel
    assert any(f.rule == "JGL012" for f in serial[0])


def test_helper_reached_only_from_thread_entry_is_single_role():
    # "main" seeds only at call-graph sources: a helper reached solely
    # through a thread entry has exactly that thread's role, so its
    # single-writer state is not a race.
    src = '''
import threading

class Svc:
    def __init__(self):
        self.count = 0
        self._worker = threading.Thread(target=self._run)

    def _run(self):
        self._bump()

    def _bump(self):
        self.count = self.count + 1
'''
    assert not [f for f in run_source(src) if f.rule == "JGL012"]


def test_imported_name_does_not_resolve_to_unrelated_module():
    # 'from vendor import flush' (vendor unanalyzed) must not absorb
    # into an unrelated module-level flush() and invent a lock edge.
    mod_a = '''
import threading
from vendor import flush

_alock = threading.Lock()

def drain():
    with _alock:
        flush()
'''
    mod_b = '''
import threading

_block = threading.Lock()

def flush():
    with _block:
        other()

def other():
    with _block:
        pass
'''
    findings = run_project_sources({"a.py": mod_a, "b.py": mod_b})
    assert not [f for f in findings if f.rule == "JGL011"]


def test_thread_annotation_above_decorator_stack_is_honored():
    src = '''
import threading

class Proc:
    def __init__(self):
        self._pending = None

    # graft: thread=step
    @staticmethod
    def tick():
        pass

    # graft: thread=step
    def on_complete(self, window):
        self._pending = window

    def apply(self):
        policy, self._pending = self._pending, None
'''
    assert [f for f in run_source(src) if f.rule == "JGL012"]


def test_jgl011_message_carries_no_counter_line_number():
    # Baseline matching is line-insensitive (path, rule, message); a
    # counter-site line in the message would break that contract.
    import re

    findings = run_project_sources(
        {"batcher.py": _BATCHER_MOD, "pipeline.py": _PIPELINE_MOD}
    )
    for f in findings:
        if f.rule == "JGL011":
            assert not re.search(r"\.py:\d", f.message), f.message


# -- baseline + SARIF (CI gating surfaces) ---------------------------------


def test_baseline_roundtrip_and_stale_reporting(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(POSITIVE["JGL007"])
    baseline = tmp_path / "baseline.json"
    # Snapshot, then the same tree gates green against it.
    assert cli_main(
        [str(dirty), "--baseline", str(baseline), "--write-baseline"]
    ) == 0
    assert cli_main([str(dirty), "--baseline", str(baseline)]) == 0
    capsys.readouterr()
    # A NEW finding still fails, reported alone.
    dirty.write_text(POSITIVE["JGL007"] + "\nimport time\nasync def f():\n    time.sleep(1)\n")
    assert cli_main([str(dirty), "--baseline", str(baseline)]) == 1
    out = capsys.readouterr().out
    assert "JGL005" in out and "JGL007" not in out
    # Fixing the baselined finding reports the entry as stale.
    dirty.write_text("x = 1\n")
    assert cli_main([str(dirty), "--baseline", str(baseline)]) == 0
    err = capsys.readouterr().err
    assert "stale baseline entry" in err


def test_missing_baseline_file_fails_the_gate(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli_main(
        [str(clean), "--baseline", str(tmp_path / "nope.json")]
    ) == 1


def test_write_baseline_refuses_partly_unreadable_tree(tmp_path):
    # A snapshot over a tree with parse errors would under-record and
    # later mask findings; nothing may be written.
    (tmp_path / "ok.py").write_text(POSITIVE["JGL007"])
    (tmp_path / "broken.py").write_text("def broken(:\n")
    baseline = tmp_path / "baseline.json"
    assert cli_main(
        [str(tmp_path), "--baseline", str(baseline), "--write-baseline"]
    ) == 1
    assert not baseline.exists()


def test_sarif_report_written_even_when_failing(tmp_path):
    import json

    dirty = tmp_path / "dirty.py"
    dirty.write_text(POSITIVE["JGL007"])
    sarif = tmp_path / "out.sarif"
    assert cli_main([str(dirty), "--sarif", str(sarif)]) == 1
    doc = json.loads(sarif.read_text())
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    results = run["results"]
    assert results and results[0]["ruleId"] == "JGL007"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("dirty.py")
    assert loc["region"]["startLine"] > 0
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "JGL011" in rule_ids  # whole-program rules carry metadata too


# -- the dataflow rules (JGL021-024, docs/adr/0119) ------------------------


def test_jgl022_guards_all_five_note_state_lost_sites():
    """The ISSUE 12 acceptance proof: job_manager.py's five containment
    sites are individually covered — deleting ANY one note_state_lost()
    call in a scratch copy makes JGL022 fire, and the intact file is
    clean. The sixth site someone adds next PR cannot silently skip the
    epoch discipline."""
    src = (
        REPO / "src" / "esslivedata_tpu" / "core" / "job_manager.py"
    ).read_text(encoding="utf-8")
    assert not [
        f
        for f in run_source(src, path="job_manager.py")
        if f.rule == "JGL022"
    ]
    lines = src.split("\n")
    sites = [
        i for i, line in enumerate(lines) if "note_state_lost()" in line
    ]
    assert len(sites) == 5, (
        "the five-site inventory moved; update this test AND the ADR"
    )
    for i in sites:
        mutated = "\n".join(lines[:i] + lines[i + 1:])
        fired = [
            f
            for f in run_source(mutated, path="job_manager.py")
            if f.rule == "JGL022"
        ]
        assert fired, f"deleting the note at line {i + 1} did not fire"


def test_jgl021_traced_value_must_actually_be_traced():
    # The taint is dataflow-based: rebinding the name to host data
    # AFTER the traced use washes it before the store.
    src = '''
import jax
import jax.numpy as jnp

class Hist:
    @jax.jit
    def step(self, state, batch):
        total = jnp.sum(batch)
        total = 0
        self.last_total = total
        return state
'''
    assert not [f for f in run_source(src) if f.rule == "JGL021"]


def test_jgl021_module_container_escape_fires():
    src = '''
import jax
import jax.numpy as jnp

TRACE_LOG = []

@jax.jit
def fold(batch):
    total = jnp.sum(batch)
    TRACE_LOG.append(total)
    return total
'''
    assert [f for f in run_source(src) if f.rule == "JGL021"]


def test_jgl023_acquire_release_pairing_is_seen():
    src = '''
import os

class Plane:
    def checkpoint(self, f):
        self._lock.acquire()
        try:
            os.fsync(f.fileno())
        finally:
            self._lock.release()
'''
    assert [f for f in run_source(src) if f.rule == "JGL023"]


def test_jgl023_locked_convention_judged_at_call_site():
    quiet = '''
import os

class Plane:
    def _flush_locked(self, f):
        os.fsync(f.fileno())
'''
    assert not [f for f in run_source(quiet) if f.rule == "JGL023"]
    caller = quiet + '''
import threading

class Svc:
    def __init__(self):
        self._lock = threading.Lock()
        self._plane = Plane()

    def tick(self, f):
        with self._lock:
            self._plane._flush_locked(f)
'''
    fired = [f for f in run_source(caller) if f.rule == "JGL023"]
    assert fired and "_flush_locked" in fired[0].message


def test_jgl023_blocking_after_lock_release_is_quiet():
    src = '''
import os
import threading

class Plane:
    def __init__(self):
        self._lock = threading.Lock()

    def checkpoint(self, f):
        with self._lock:
            entries = list(self._pending)
        os.fsync(f.fileno())
'''
    assert not [f for f in run_source(src) if f.rule == "JGL023"]


def test_jgl024_file_wide_stale_reported_at_directive():
    src = '''
x = 1

# graftlint: disable-file=JGL006 vestigial
y = 2
'''
    fired = [f for f in run_source(src) if f.rule == "JGL024"]
    assert fired and fired[0].line == 4


def test_jgl024_not_judged_when_rule_deselected():
    src = '''
def healthy():
    return 1  # graftlint: disable=JGL007 vestigial
'''
    # JGL007 did not run, so its absence proves nothing.
    quiet = run_source(src, select=frozenset({"JGL024"}))
    assert not quiet
    # With both selected the staleness IS judged.
    fired = run_source(src, select=frozenset({"JGL007", "JGL024"}))
    assert [f for f in fired if f.rule == "JGL024"]


def test_jgl024_unknown_rule_id_is_always_stale():
    src = '''
x = 1  # graftlint: disable=JGL999
'''
    fired = [f for f in run_source(src) if f.rule == "JGL024"]
    assert fired and "no such rule" in fired[0].message


def test_jobs_parallel_matches_serial_dataflow_rules(tmp_path):
    """The jobs-parity contract extended to the dataflow rules: BlockFact
    extraction and the meta pass must produce identical findings whether
    facts were extracted in-process or shipped back from workers."""
    (tmp_path / "a.py").write_text(POSITIVE["JGL021"])
    (tmp_path / "b.py").write_text(POSITIVE["JGL022"])
    (tmp_path / "c.py").write_text(POSITIVE["JGL023"])
    (tmp_path / "d.py").write_text(POSITIVE["JGL024"])
    serial = run_paths([str(tmp_path)], jobs=1)
    parallel = run_paths([str(tmp_path)], jobs=2)
    assert serial == parallel
    rules_seen = {f.rule for f in serial[0]}
    assert {"JGL021", "JGL022", "JGL023", "JGL024"} <= rules_seen


def test_full_tree_perf_budget_and_jobs_determinism():
    """The CI perf budget (ISSUE 12): a full src/ run with all rules —
    CFGs, lock regions, taint and the meta pass included — stays well
    inside the pre-commit attention span, and the finding set is
    byte-identical across --jobs settings (facts are picklable value
    objects; no analysis may depend on process-local state)."""
    import time

    src_tree = str(REPO / "src" / "esslivedata_tpu")
    t0 = time.perf_counter()
    serial = run_paths([src_tree], jobs=1)
    elapsed = time.perf_counter() - t0
    # ~0.8 s today on this container; 60 s is the do-not-cross line
    # (generous so slow CI machines do not flake, tight enough that an
    # accidentally-quadratic rule still fails loudly).
    assert elapsed < 60.0, f"full-tree lint took {elapsed:.1f}s"
    parallel = run_paths([src_tree], jobs=4)
    assert serial == parallel


def test_changed_only_mode(tmp_path):
    """--diff BASE lints exactly the files changed vs the ref (plus
    untracked), and fails the gate on a bad ref instead of silently
    linting nothing."""
    import subprocess

    from tools.graftlint.cli import changed_python_files

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=tmp_path, check=True,
            capture_output=True,
        )

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "clean.py").write_text("x = 1\n")
    (tmp_path / "dirty.py").write_text("y = 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    (tmp_path / "dirty.py").write_text(POSITIVE["JGL007"])
    (tmp_path / "fresh.py").write_text("z = 1\n")  # untracked

    import os

    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        changed = changed_python_files([str(tmp_path)], "HEAD")
        rc_hit = cli_main(["--diff", "HEAD", str(tmp_path), "-q"])
        rc_bad = cli_main(["--diff", "no-such-ref", str(tmp_path)])
    finally:
        os.chdir(cwd)
    names = {Path(p).name for p in changed}
    assert names == {"dirty.py", "fresh.py"}
    assert rc_hit == 1  # the JGL007 in dirty.py is seen
    assert rc_bad == 1  # bad ref fails the gate


def test_changed_only_clean_diff_is_green(tmp_path, capsys):
    import os
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=tmp_path, check=True,
            capture_output=True,
        )

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = cli_main(["--changed-only", str(tmp_path)])
    finally:
        os.chdir(cwd)
    assert rc == 0
    assert "nothing to lint" in capsys.readouterr().out


def test_jgl023_interprocedural_sees_acquire_release_locks():
    # Regression (review): CallFact.held must include acquire/release-
    # paired locks, not just lexical `with` blocks — a call made
    # between acquire() and release() into a may-block function is the
    # manual-protocol shape of the same hazard.
    src = '''
import os

class Plane:
    def checkpoint(self, f):
        self._lock.acquire()
        try:
            self._dump(f)
        finally:
            self._lock.release()

    def _dump(self, f):
        os.fsync(f.fileno())
'''
    fired = [f for f in run_source(src) if f.rule == "JGL023"]
    assert fired and "os.fsync" in fired[0].message


def test_jgl021_noop_augment_does_not_wash_taint():
    # Regression (review): `total += 0` rebinds the name but READS it
    # too — the taint must flow through the augmented assignment.
    src = '''
import jax
import jax.numpy as jnp

class Hist:
    @jax.jit
    def step(self, state, batch):
        total = jnp.sum(batch)
        total += 0
        self.last_total = total
        return state
'''
    assert [f for f in run_source(src) if f.rule == "JGL021"]


def test_suppression_audit_skipped_when_audit_off():
    # Regression (review): in diff mode the project pass sees a partial
    # view, so project-rule suppressions would look stale — missing
    # findings must not CREATE findings. run_paths(audit=False) is the
    # switch the CLI throws for --diff/--changed-only.
    src = POSITIVE["JGL012"].replace(
        "self.count = self.count + 1",
        "self.count = self.count + 1  "
        "# graftlint: disable=JGL012 single-writer handshake",
    ).replace(
        "def poll(self):\n        self.count = 0",
        "def poll(self):\n        self.count = 0  "
        "# graftlint: disable=JGL012 single-writer handshake",
    )
    # Strip the thread entry: without it JGL012 cannot fire at all, so
    # on a full view both directives would be stale...
    partial = src.replace(
        "        self._worker = threading.Thread(target=self._run)\n", ""
    )
    import tempfile
    from pathlib import Path as _P

    with tempfile.TemporaryDirectory() as d:
        p = _P(d) / "mod.py"
        p.write_text(partial)
        audited, _ = run_paths([str(p)])
        silent, _ = run_paths([str(p)], audit=False)
    assert any(f.rule == "JGL024" for f in audited)
    assert not [f for f in silent if f.rule == "JGL024"]


def test_jgl023_interproc_adopts_deterministic_callee():
    # Regression (review): the (op, site) adopted through the may-block
    # closure must come from the sorted-first blocking callee, not
    # hash order — baseline matching is message-keyed.
    src = '''
import os
import threading

class P:
    def __init__(self):
        self._lock = threading.Lock()

    def a_block(self, f):
        os.fsync(f.fileno())

    def b_block(self, f):
        os.replace("a", "b")

    def helper(self, f):
        self.a_block(f)
        self.b_block(f)

    def hot(self, f):
        with self._lock:
            self.helper(f)
'''
    fired = [f for f in run_source(src) if f.rule == "JGL023"]
    assert len(fired) == 1
    assert "os.fsync" in fired[0].message  # a_block sorts first


def test_changed_only_no_untracked_excludes_scratch_files(tmp_path):
    # Regression (review): pre-commit stashes unstaged tracked work but
    # NOT untracked files — a scratch file with a finding must not
    # block an unrelated commit when --no-untracked is passed.
    import os
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=tmp_path, check=True,
            capture_output=True,
        )

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    (tmp_path / "scratch.py").write_text(POSITIVE["JGL007"])  # untracked
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc_hook = cli_main(
            ["--changed-only", "--no-untracked", str(tmp_path), "-q"]
        )
        rc_dev = cli_main(["--changed-only", str(tmp_path), "-q"])
    finally:
        os.chdir(cwd)
    assert rc_hook == 0  # scratch file ignored: commit not blocked
    assert rc_dev == 1  # interactive default still sees it


def test_jgl022_finally_guaranteed_note_is_quiet():
    # Regression (review): a note_state_lost() in a finally block runs
    # on EVERY exit from the try — including an early return from the
    # containment branch — so the reset is protocol-compliant.
    src = '''
class M:
    def handle(self):
        try:
            self.work()
        except Exception:
            if self.consumed():
                self.offer.reset()
                return None
        finally:
            self.job.note_state_lost()
'''
    assert not [f for f in run_source(src) if f.rule == "JGL022"]


def test_jgl022_raise_path_in_try_finally_still_fires():
    # Regression (review): raise inside a handler-less try must keep
    # its exceptional path in the CFG — a note-free finally does not
    # satisfy the protocol, and the reset must still be flagged.
    src = '''
class M:
    def f(self, res):
        try:
            if res.state_lost:
                self.offer.reset()
                raise RuntimeError("x")
        finally:
            self.log()

    def other(self, rec):
        rec.job.note_state_lost()
'''
    assert [f for f in run_source(src) if f.rule == "JGL022"]


def test_jgl022_note_before_reset_is_compliant():
    # Regression (review): the protocol event may be written in either
    # order — a note that DOMINATES the reset (every path into the
    # reset already passed it) is as compliant as one that follows.
    src = '''
class M:
    def recover(self, rec, offer):
        if offer.state_lost:
            rec.job.note_state_lost()
            offer.reset()
'''
    assert not [f for f in run_source(src) if f.rule == "JGL022"]


def test_jgl023_sees_blocking_inside_worker_closures():
    # Regression (review): the worker-closure thread target is this
    # codebase's dominant threading idiom — a with-lock fsync inside
    # one must fire the direct half.
    src = '''
import os
import threading

class Svc:
    def __init__(self):
        self._lock = threading.Lock()

    def start(self, f):
        def _run():
            with self._lock:
                os.fsync(f.fileno())
        threading.Thread(target=_run).start()
'''
    assert [f for f in run_source(src) if f.rule == "JGL023"]


def test_jgl023_one_finding_when_direct_and_interproc_agree():
    # Regression (review): a serialize-named call that also resolves to
    # an in-project may-block function is ONE hazard, not two.
    src = '''
import os
import threading

class Sink:
    def serialize(self, data):
        os.fsync(data.fileno())

class Svc:
    def __init__(self):
        self._lock = threading.Lock()
        self._sink = Sink()

    def hot(self, data):
        with self._lock:
            self._sink.serialize(data)
'''
    assert len([f for f in run_source(src) if f.rule == "JGL023"]) == 1


def test_diff_mode_suppresses_stale_baseline_report(tmp_path, capsys):
    # Regression (review): diff-mode runs see only changed files, so a
    # baseline entry for an UNCHANGED file must not be reported stale
    # (pruning it would resurrect the finding in the full-tree run).
    import json
    import os
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=tmp_path, check=True,
            capture_output=True,
        )

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    unchanged = tmp_path / "unchanged.py"
    unchanged.write_text(POSITIVE["JGL007"])
    (tmp_path / "other.py").write_text("x = 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    findings = run_paths([str(unchanged)])[0]
    assert findings
    baseline = tmp_path / "b.json"
    baseline.write_text(json.dumps({
        "version": 1,
        "findings": [
            {"path": f.path, "rule": f.rule, "message": f.message}
            for f in findings
        ],
    }))
    (tmp_path / "other.py").write_text("x = 2\n")  # the only change
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        rc = cli_main(
            ["--changed-only", "--baseline", str(baseline),
             str(tmp_path)]
        )
    finally:
        os.chdir(cwd)
    err = capsys.readouterr().err
    assert rc == 0
    assert "stale baseline" not in err


# -- --explain and the trace-pass CLI surface (ADR 0123) --------------------


def test_cli_explain_prints_rule_doc(capsys):
    assert cli_main(["--explain", "JGL102"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("### JGL102")
    # The doc section ships its minimal bad/good example.
    assert "# bad" in out and "# good" in out


def test_cli_explain_static_rule_too(capsys):
    assert cli_main(["--explain", "JGL001"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("### JGL001")


def test_cli_explain_unknown_rule_is_a_usage_error(capsys):
    with pytest.raises(SystemExit) as exc:
        cli_main(["--explain", "JGL999"])
    assert exc.value.code == 2
    assert "unknown rule id" in capsys.readouterr().err


def test_explain_falls_back_to_summary_without_docs(tmp_path):
    from tools.graftlint.explain import explain

    missing = tmp_path / "no_such_docs.md"
    text = explain("JGL102", docs_path=missing)
    assert text is not None
    assert RULES["JGL102"].summary in text
    assert "no docs/graftlint.md section yet" in text


def test_list_rules_includes_trace_scope(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in (
        "JGL100", "JGL101", "JGL102", "JGL103", "JGL104", "JGL105",
    ):
        assert rule_id in out


def test_trace_rules_registered_with_trace_scope():
    trace_rules = {r for r, rule in RULES.items() if rule.scope == "trace"}
    assert trace_rules == {
        "JGL100", "JGL101", "JGL102", "JGL103", "JGL104", "JGL105",
    }


# -- JGL024 judges the trace suppression ledger (ADR 0123) ------------------


def _trace_finding(path, line):
    from tools.graftlint.findings import Finding

    return Finding(
        str(path), line, "JGL104", "fixture: host callback in traced body"
    )


def test_jgl024_trace_directive_live_when_finding_present(tmp_path):
    # The directive masks a real trace finding this run produced: it
    # earns its keep, so neither the finding nor JGL024 survives.
    f = tmp_path / "w.py"
    f.write_text("X = 1  # graftlint: disable=JGL104\n")
    findings, errors = run_paths(
        [str(f)], extra_findings=[_trace_finding(f, 1)]
    )
    assert errors == []
    assert findings == []


def test_jgl024_trace_directive_stale_when_trace_ran_clean(tmp_path):
    # The trace pass ran (select=None implies every scope) and found
    # nothing behind the directive: it is dead weight, JGL024 fires.
    f = tmp_path / "w.py"
    f.write_text("X = 1  # graftlint: disable=JGL104\n")
    findings, errors = run_paths([str(f)])
    assert errors == []
    assert [x.rule for x in findings] == ["JGL024"]
    assert "JGL104" in findings[0].message


def test_jgl024_trace_directive_not_judged_when_trace_skipped(tmp_path):
    # The CLI's no-trace select: all rules minus the trace scope. A
    # run that produced no trace findings BECAUSE the pass did not run
    # must not call the directive stale (the diff-mode inversion).
    f = tmp_path / "w.py"
    f.write_text("X = 1  # graftlint: disable=JGL104\n")
    no_trace = frozenset(
        r for r, rule in RULES.items() if rule.scope != "trace"
    )
    findings, errors = run_paths([str(f)], select=no_trace)
    assert errors == []
    assert findings == []


def test_cli_trace_findings_ride_baseline_and_suppressions(tmp_path, capsys):
    # End to end through the CLI plumbing (monkeypatch-free trace run
    # is covered in graftlint_trace_test.py; here the wiring): a fake
    # trace report's findings must reach the normal findings stream.
    import tools.graftlint.trace as trace_pkg
    from tools.graftlint.trace.engine import TraceReport

    f = tmp_path / "w.py"
    f.write_text("X = 1\n")
    real = trace_pkg.run_trace
    trace_pkg.run_trace = lambda **kw: TraceReport(
        findings=[_trace_finding(f, 1)]
    )
    try:
        rc = cli_main([str(f), "--trace"])
    finally:
        trace_pkg.run_trace = real
    out = capsys.readouterr().out
    assert rc == 1
    assert "JGL104" in out


def test_cli_trace_skip_is_visible(tmp_path, capsys):
    import tools.graftlint.trace as trace_pkg
    from tools.graftlint.trace.engine import TraceReport

    f = tmp_path / "w.py"
    f.write_text("X = 1\n")
    real = trace_pkg.run_trace
    trace_pkg.run_trace = lambda **kw: TraceReport(
        skipped="jax unavailable (No module named 'jax')"
    )
    try:
        rc = cli_main([str(f), "--trace"])
    finally:
        trace_pkg.run_trace = real
    err = capsys.readouterr().err
    assert rc == 0  # static gates still apply; the skip is loud, not fatal
    assert "trace pass SKIPPED" in err
