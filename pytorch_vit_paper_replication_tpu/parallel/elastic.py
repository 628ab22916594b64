"""Elastic preemption-tolerant training: survive a killed worker mid-epoch.

The supervisor/heartbeat layer behind ``train.py --elastic N`` (ISSUE 11,
ROADMAP item 3). A TPU pod loses hosts without warning; before this layer
a SIGKILLed worker took the whole job with it. Now:

* every worker writes an atomic heartbeat file (slot, pid, generation,
  step) into a shared **rendezvous directory** on a cadence;
* an :class:`ElasticSupervisor` spawns the N worker processes, watches
  heartbeats + child exits, and on a loss **re-forms the cluster on the
  survivors**: membership generation bumps, the dead generation's
  collectives are broken so blocked survivors fail fast, survivors exit,
  and a shrunken generation (``dp`` axis down one host) respawns —
  restoring the last rotating checkpoint *through the persistent compile
  cache* (PR 4: restart TTFS is a cache read, not a full XLA compile)
  and re-sharding the streaming loader to the new ``process_count`` at
  the restored step. When the lost host rejoins, the same mechanism
  scales back up at a step boundary with a clean checkpoint handoff;
* two cluster **backends** share the layer: ``jax`` drives a real
  ``jax.distributed`` pod (re-init with retry/backoff —
  :func:`..mesh.initialize_multi_host`), while ``host`` runs each worker
  as an independent single-process JAX instance and sums gradients
  across workers through a TCP :class:`AllReduceServer` in the
  supervisor — genuinely multi-process data parallelism that runs on
  any host (this is what the 2-process CPU evidence runs and tier-1
  tests exercise).

Correctness core: a checkpoint written at ``dp=N`` restores onto a
``dp=N-1`` mesh bit-faithfully — :meth:`..checkpoint.Checkpointer.restore`
adopts the fresh state's shardings, and ``tests/test_elastic.py`` pins
the dp=4 -> dp=2 case (bit-equal params, identical next-step loss).
Loss-trajectory equivalence of a killed-and-recovered run vs an unkilled
control is gated end-to-end by ``tools/elastic_bench.py``
(``elastic_ok`` on bench.py's compact gates line, evidence
``runs/elastic_r13/``).

Worker exit codes are part of the protocol: ``EXIT_YIELD`` (75) means
"checkpointed and stepped aside for a re-formation", ``EXIT_COLLECTIVE``
(76) means "a collective failed under me" — the supervisor treats both
as expected during a reform and anything else as a worker loss.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.atomic import atomic_write_json

# Worker exit codes the supervisor recognizes as protocol, not crashes.
EXIT_YIELD = 75        # EX_TEMPFAIL: saved + stepped aside for a reform
EXIT_COLLECTIVE = 76   # EX_PROTOCOL: a collective failed under the worker

MEMBERSHIP_NAME = "membership.json"
LOSSES_NAME = "losses.jsonl"
SUPERVISOR_NAME = "supervisor.json"


class CollectiveFailure(RuntimeError):
    """A host-collective op could not complete (peer lost / generation
    broken). The worker's state at the last applied step is still valid —
    the failed step contributed nothing — so the primary may checkpoint
    it before exiting."""


# --------------------------------------------------------------------------
# Rendezvous files: heartbeats + membership (atomic small-file manifests).
# --------------------------------------------------------------------------

def heartbeat_path(rendezvous: str | Path, slot: int) -> Path:
    return Path(rendezvous) / f"heartbeat_{slot}.json"


def write_heartbeat(rendezvous: str | Path, slot: int, *, generation: int,
                    step: int, pid: Optional[int] = None) -> Path:
    """Atomic per-slot liveness manifest: the supervisor reads staleness,
    the fault-injection harness reads (pid, step) to aim its kills."""
    return atomic_write_json(heartbeat_path(rendezvous, slot), {
        "slot": slot, "pid": pid if pid is not None else os.getpid(),
        "generation": generation, "step": step, "time": time.time()})


def read_heartbeats(rendezvous: str | Path) -> Dict[int, dict]:
    out: Dict[int, dict] = {}
    for p in sorted(Path(rendezvous).glob("heartbeat_*.json")):
        try:
            hb = json.loads(p.read_text())
            out[int(hb["slot"])] = hb
        except (ValueError, KeyError, OSError):
            continue  # torn/half-gone heartbeat: treat as absent this poll
    return out


def write_membership(rendezvous: str | Path, *, generation: int,
                     process_count: int, reason: str = "") -> Path:
    """The supervisor's single source of truth for the CURRENT target
    cluster. Workers spawned at generation g re-form (yield at the next
    step boundary) whenever the file's generation exceeds g."""
    return atomic_write_json(Path(rendezvous) / MEMBERSHIP_NAME, {
        "generation": generation, "process_count": process_count,
        "reason": reason, "time": time.time()})


def read_membership(rendezvous: str | Path) -> Optional[dict]:
    p = Path(rendezvous) / MEMBERSHIP_NAME
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError):
        return None


def latest_checkpoint_step(checkpoint_dir: str | Path) -> Optional[int]:
    """Latest COMMITTED orbax step under ``checkpoint_dir`` without
    constructing a CheckpointManager (the supervisor reads this between
    generations to price lost work; an async save killed mid-flight
    leaves no metadata file and is correctly invisible)."""
    best = None
    root = Path(checkpoint_dir)
    if not root.is_dir():
        return None
    for child in root.iterdir():
        if child.is_dir() and child.name.isdigit() and (
                child / "_CHECKPOINT_METADATA").exists():
            best = max(best, int(child.name)) if best is not None \
                else int(child.name)
    return best


# --------------------------------------------------------------------------
# Host collective: TCP allreduce through the supervisor (the CPU-cluster
# backend; on real pods the mesh's psum does this job inside XLA).
# --------------------------------------------------------------------------

def _send_frame(sock: socket.socket, header: dict,
                payload: bytes = b"") -> None:
    raw = json.dumps(header, separators=(",", ":")).encode()
    sock.sendall(struct.pack(">I", len(raw)) + raw
                 + struct.pack(">Q", len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf += chunk
    return buf


def _recv_frame(sock: socket.socket) -> Tuple[dict, bytes]:
    header = json.loads(_recv_exact(
        sock, struct.unpack(">I", _recv_exact(sock, 4))[0]))
    payload = _recv_exact(
        sock, struct.unpack(">Q", _recv_exact(sock, 8))[0])
    return header, payload


class AllReduceServer:
    """Sum-allreduce rendezvous for one generation of workers.

    Each member holds one persistent connection; per op it contributes a
    float32 vector tagged (generation, seq) and blocks until every member
    of the generation contributed, then receives the sum. Contributions
    are summed in ascending-slot order so the result is independent of
    arrival order (bit-deterministic across runs). A member lost
    mid-epoch breaks the generation: every blocked peer gets an error
    frame immediately instead of hanging on a dead socket — the "failed
    collective" detection leg of worker-loss handling.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(16)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._generation = -1
        self._count = 0
        self._broken: Dict[int, str] = {}
        self._contrib: Dict[Tuple[int, int], Dict[int, np.ndarray]] = {}
        self._results: Dict[Tuple[int, int], np.ndarray] = {}
        self._fetched: Dict[Tuple[int, int], int] = {}
        self._closed = False
        self._threads: List[threading.Thread] = []
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="allreduce-accept", daemon=True)
        self._accept_thread.start()

    @property
    def address(self) -> str:
        host, port = self._sock.getsockname()
        return f"{host}:{port}"

    def set_generation(self, generation: int, count: int) -> None:
        """Open a new generation of `count` members; pending state of
        older generations is dropped (their members are gone)."""
        with self._cond:
            self._generation = generation
            self._count = count
            self._contrib = {k: v for k, v in self._contrib.items()
                             if k[0] == generation}
            self._results = {k: v for k, v in self._results.items()
                             if k[0] == generation}
            self._fetched = {k: v for k, v in self._fetched.items()
                             if k[0] == generation}
            self._cond.notify_all()

    def break_generation(self, generation: int,
                         reason: str = "member lost") -> None:
        """Fail every pending and future op of `generation` fast."""
        with self._cond:
            self._broken[generation] = reason
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._broken[self._generation] = "server closed"
            self._cond.notify_all()
        try:
            self._sock.close()
        except OSError:
            pass

    # ---------------------------------------------------- internals
    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # closed
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name="allreduce-conn", daemon=True)
            t.start()
            self._threads.append(t)

    def _serve_conn(self, conn: socket.socket) -> None:
        slot = gen = None
        try:
            hello, _ = _recv_frame(conn)
            slot, gen = int(hello["slot"]), int(hello["generation"])
            _send_frame(conn, {"ok": 1})
            while True:
                header, payload = _recv_frame(conn)
                seq = int(header["seq"])
                vec = np.frombuffer(payload, np.float32).copy()
                result = self._reduce(gen, seq, slot, vec)
                if result is None:
                    _send_frame(conn, {"ok": 0, "seq": seq,
                                       "err": self._broken.get(
                                           gen, "generation closed")})
                else:
                    _send_frame(conn, {"ok": 1, "seq": seq},
                                result.tobytes())
        except (ConnectionError, OSError, ValueError, KeyError):
            pass
        finally:
            # A dropped member breaks its generation: peers blocked on
            # the next op must fail fast, not wait out a TCP timeout.
            if gen is not None and not self._closed:
                with self._cond:
                    sealed = gen < self._generation
                if not sealed:
                    self.break_generation(gen, f"slot {slot} connection "
                                               "lost")
            try:
                conn.close()
            except OSError:
                pass

    def _reduce(self, gen: int, seq: int, slot: int,
                vec: np.ndarray) -> Optional[np.ndarray]:
        key = (gen, seq)
        with self._cond:
            if gen in self._broken:
                return None
            self._contrib.setdefault(key, {})[slot] = vec
            if len(self._contrib[key]) == self._count:
                # Ascending-slot summation: result independent of
                # arrival order, so reruns are bit-deterministic.
                parts = self._contrib.pop(key)
                total = np.zeros_like(vec, np.float32)
                for s in sorted(parts):
                    total = total + parts[s]
                self._results[key] = total
                self._fetched[key] = 0
                self._cond.notify_all()
            while key not in self._results:
                if gen in self._broken:
                    return None
                self._cond.wait(timeout=1.0)
            out = self._results[key]
            self._fetched[key] += 1
            if self._fetched[key] >= self._count:
                del self._results[key], self._fetched[key]
            return out


class HostCollective:
    """Worker-side client of :class:`AllReduceServer` (one connection,
    lockstep sequence numbers — every member issues the same ops in the
    same order, which the SPMD training loop guarantees)."""

    def __init__(self, address: str, *, slot: int, generation: int,
                 timeout_s: float = 600.0):
        host, port = address.rsplit(":", 1)
        self.slot, self.generation = slot, generation
        self._seq = 0
        self._sock = socket.create_connection((host, int(port)),
                                              timeout=timeout_s)
        _send_frame(self._sock, {"slot": slot, "generation": generation})
        ack, _ = _recv_frame(self._sock)
        if not ack.get("ok"):
            raise CollectiveFailure(f"handshake refused: {ack}")

    def allreduce(self, vec: np.ndarray) -> np.ndarray:
        """Sum `vec` (float32) across every member of the generation."""
        self._seq += 1
        data = np.ascontiguousarray(vec, np.float32)
        try:
            _send_frame(self._sock, {"seq": self._seq}, data.tobytes())
            header, payload = _recv_frame(self._sock)
        except (OSError, ConnectionError, socket.timeout) as e:
            raise CollectiveFailure(f"allreduce transport failed: {e}") \
                from e
        if not header.get("ok"):
            raise CollectiveFailure(
                f"allreduce seq {self._seq} failed: "
                f"{header.get('err', 'unknown')}")
        return np.frombuffer(payload, np.float32).reshape(data.shape)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


# --------------------------------------------------------------------------
# Host-collective step functions (the dp-across-processes math for the
# `host` backend; the `jax` backend keeps parallel.api's mesh steps).
# --------------------------------------------------------------------------

def make_host_collective_train_step(
        state, *, collective: Optional[HostCollective],
        label_smoothing: float = 0.0, nan_guard: bool = False,
        on_step: Optional[Callable[[int, float], None]] = None):
    """``(state, batch) -> (state, metrics)`` where gradients are summed
    across worker processes through `collective` before ONE optimizer
    update applies the global gradient — the same math as a dp-mesh psum,
    with the reduction moved to the host because this backend's workers
    are independent JAX processes.

    The local jit computes grad of the SUM of per-example losses (plus
    loss/correct/count sums) as one flat float32 vector; the host
    allreduces it; a second jit divides by the global count, runs the
    optax chain (clip + Adam + schedule all see the GLOBAL gradient),
    and applies the update. Every worker applies identical updates to
    identical params, so state stays replicated bit-for-bit across the
    cluster. The per-step device_get IS the collective on this backend
    (deliberate host sync, exactly where a pod's psum would block).

    `on_step` is called with ``(step, global_mean_loss)`` after each
    applied step — the loss-trajectory recorder.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.flatten_util import ravel_pytree

    _, unravel = ravel_pytree(state.params)

    def _local(state, batch):
        dropout_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            logits = state.apply_fn(
                {"params": params}, batch["image"], True,
                rngs={"dropout": dropout_rng}).astype(jnp.float32)
            labels = batch["label"]
            if label_smoothing > 0.0:
                onehot = optax.smooth_labels(
                    jax.nn.one_hot(labels, logits.shape[-1]),
                    label_smoothing)
                losses = optax.softmax_cross_entropy(logits, onehot)
            else:
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels)
            return losses.sum(), logits

        (loss_sum, logits), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        flat, _ = ravel_pytree(grads)
        pred = jnp.argmax(logits, axis=-1)
        tail = jnp.stack([
            loss_sum,
            jnp.sum(pred == batch["label"]).astype(jnp.float32),
            jnp.asarray(batch["label"].shape[0], jnp.float32)])
        return jnp.concatenate([flat.astype(jnp.float32), tail])

    def _apply(state, flat_sum, loss_sum, correct, count):
        grads = unravel(flat_sum / count)
        updates, opt_state = state.tx.update(grads, state.opt_state,
                                             state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = {"loss_sum": loss_sum, "correct": correct,
                   "count": count, "grad_norm": optax.global_norm(grads)}
        if nan_guard:
            ok = jnp.isfinite(loss_sum) & jnp.isfinite(
                metrics["grad_norm"])
            keep = lambda new, old: jax.tree.map(          # noqa: E731
                lambda n, o: jnp.where(ok, n, o), new, old)
            params = keep(params, state.params)
            opt_state = keep(opt_state, state.opt_state)
            metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                       for k, v in metrics.items()}
            metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
        new_state = state.replace(step=state.step + 1, params=params,
                                  opt_state=opt_state)
        return new_state, metrics

    local_fn = jax.jit(_local)
    # NO donate_argnums on the apply jit, deliberately: on the CPU
    # backend a DESERIALIZED (persistent-compile-cache-hit) executable
    # with donated inputs was seen to corrupt the heap when run against
    # orbax-restored arrays ("corrupted double-linked list"/SIGSEGV a
    # couple of steps after resume) — exactly the restore-through-the-
    # cache path every elastic recovery takes. Found by the
    # fault-injection harness: each respawned generation died ~1 step
    # after restore until the supervisor's cache quarantine broke the
    # loop; dropping donation here removes the crash entirely
    # (reproduced/verified by 4 consecutive save->restore->cache-hit
    # round-trips). Cost: one extra params+opt_state buffer per step on
    # the HOST backend only — pods use the jax backend's normal donated
    # mesh step.
    apply_fn = jax.jit(_apply)
    step_box = {"step": None}

    def train_step(state, batch):
        # Host sync by design: this fetch IS the cross-process gradient
        # exchange on the host backend (a pod's psum blocks here too).
        vec = np.asarray(jax.device_get(local_fn(state, batch)),
                         np.float32)
        if collective is not None:
            vec = collective.allreduce(vec)
        flat, tail = vec[:-3], vec[-3:]
        new_state, metrics = apply_fn(
            state, jnp.asarray(flat), jnp.asarray(tail[0]),
            jnp.asarray(tail[1]), jnp.asarray(tail[2]))
        if step_box["step"] is None:
            step_box["step"] = int(jax.device_get(new_state.step))
        else:
            step_box["step"] += 1
        if on_step is not None:
            on_step(step_box["step"], float(tail[0]) / max(tail[2], 1.0))
        # The last APPLIED state, for the yield-save path: when a later
        # step's collective fails (before its apply), the training loop
        # never returns — this reference is how the primary still
        # checkpoints the boundary state. Never a donated buffer: the
        # failing step donated nothing.
        train_step.last_state = new_state
        return new_state, metrics

    train_step.last_state = None
    return train_step


def make_host_collective_eval_step(eval_step,
                                   collective: Optional[HostCollective]):
    """Wrap a local eval step so its loss/correct/count sums are reduced
    across workers per batch — every worker reports GLOBAL eval metrics
    (the lockstep eval pass is what makes the shared-seq collective
    safe: pad_shards gives every worker the same local batch count)."""
    import jax
    import jax.numpy as jnp

    def step(state, batch):
        m = eval_step(state, batch)
        vec = np.asarray(jax.device_get(jnp.stack(
            [m["loss_sum"], m["correct"], m["count"]])), np.float32)
        if collective is not None:
            vec = collective.allreduce(vec)
        return {"loss_sum": float(vec[0]), "correct": float(vec[1]),
                "count": float(vec[2])}

    return step


# --------------------------------------------------------------------------
# Worker-side context: heartbeats, membership watch, loss recording.
# --------------------------------------------------------------------------

class ElasticWorkerContext:
    """Everything a ``train.py --elastic-worker-id`` process needs beyond
    the normal training path: a heartbeat thread (liveness + the step
    the fault harness aims kills at), a membership watcher that requests
    a clean yield when the supervisor announces a new generation, the
    host-collective client, and the per-step loss trajectory recorder
    (primary slot only — the committed-evidence curve)."""

    def __init__(self, rendezvous: str | Path, *, worker_id: int,
                 process_count: int, generation: int,
                 backend: str = "host",
                 collective_address: Optional[str] = None,
                 heartbeat_s: float = 1.0,
                 collective_timeout_s: float = 600.0,
                 registry=None):
        self.rendezvous = Path(rendezvous)
        self.rendezvous.mkdir(parents=True, exist_ok=True)
        self.worker_id = int(worker_id)
        self.process_count = int(process_count)
        self.generation = int(generation)
        self.backend = backend
        self.heartbeat_s = float(heartbeat_s)
        self._collective_address = collective_address
        self._collective_timeout_s = float(collective_timeout_s)
        self._collective: Optional[HostCollective] = None
        self._reform = threading.Event()
        self._stop = threading.Event()
        self._step = 0          # GIL-atomic single-writer (train thread)
        self._thread: Optional[threading.Thread] = None
        if registry is None:
            from ..telemetry import get_registry
            registry = get_registry()
        self._registry = registry
        self._losses_fh = None

    # ------------------------------------------------------- lifecycle
    def start(self) -> "ElasticWorkerContext":
        write_heartbeat(self.rendezvous, self.worker_id,
                        generation=self.generation, step=0)
        if self.backend == "host" and self._collective_address:
            self._collective = HostCollective(
                self._collective_address, slot=self.worker_id,
                generation=self.generation,
                timeout_s=self._collective_timeout_s)
        self._thread = threading.Thread(
            target=self._heartbeat_loop, name="elastic-heartbeat",
            daemon=True)
        self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.heartbeat_s + 1.0)
        if self._collective is not None:
            self._collective.close()
        if self._losses_fh is not None:
            try:
                self._losses_fh.close()
            except OSError:
                pass

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            try:
                write_heartbeat(self.rendezvous, self.worker_id,
                                generation=self.generation,
                                step=self._step)
                self._registry.count("elastic_heartbeats_total")
            except OSError:
                continue  # rendezvous dir transiently unavailable
            m = read_membership(self.rendezvous)
            if m is not None and int(m["generation"]) > self.generation:
                self._reform.set()

    # ------------------------------------------------------- protocol
    @property
    def is_primary(self) -> bool:
        return self.worker_id == 0

    @property
    def reform_pending(self) -> bool:
        return self._reform.is_set()

    def process_info(self) -> Tuple[int, int]:
        return self.worker_id, self.process_count

    def stop_check(self, step: int) -> bool:
        """``engine.train`` stop hook: records step progress for the
        heartbeat and answers whether a re-formation was requested."""
        self._step = int(step)
        return self._reform.is_set()

    @property
    def collective(self) -> Optional[HostCollective]:
        return self._collective

    def record_loss(self, step: int, loss: float) -> None:
        """Primary-only per-step global-mean-loss trajectory (JSONL,
        append): redone steps after a restore re-log under the same step
        number, and readers keep the LAST occurrence — the applied
        trajectory — while the overlap count receipts the redone work."""
        if not self.is_primary:
            return
        if self._losses_fh is None:
            self._losses_fh = open(self.rendezvous / LOSSES_NAME, "a",
                                   buffering=1)
        self._losses_fh.write(json.dumps(
            {"step": int(step), "loss": float(loss),
             "generation": self.generation}) + "\n")

    def count_collective_failure(self) -> None:
        self._registry.count("elastic_collective_failures_total")

    def count_yield(self) -> None:
        self._registry.count("elastic_yields_total")

    def write_result(self, payload: dict) -> Path:
        return atomic_write_json(
            self.rendezvous / f"result_{self.worker_id}.json", payload)


def read_loss_trajectory(rendezvous: str | Path
                         ) -> Tuple[Dict[int, float], int]:
    """(step -> last recorded loss, redone-step count) from a rendezvous
    losses JSONL. Torn tail lines (a SIGKILL mid-write) are skipped."""
    path = Path(rendezvous) / LOSSES_NAME
    losses: Dict[int, float] = {}
    redone = 0
    if not path.is_file():
        return losses, redone
    for line in path.read_text().splitlines():
        try:
            row = json.loads(line)
            step = int(row["step"])
        except (ValueError, KeyError):
            continue
        if step in losses:
            redone += 1
        losses[step] = float(row["loss"])
    return losses, redone


# --------------------------------------------------------------------------
# Supervisor: spawn, watch, re-form, rejoin.
# --------------------------------------------------------------------------

def worker_cache_dir(argv: Sequence[str],
                     env: Optional[dict] = None) -> Path:
    """The persistent compile-cache directory the workers will use —
    ``compile_cache.configure``'s rule (jax's variable, else
    ``--compile-cache-dir``, else the in-checkout default) read off
    their argv and environment; the supervisor needs it for
    poisoned-cache quarantine."""
    from ..compile_cache import DEFAULT_CACHE_DIR, ENV_CACHE_DIR

    raw = (env if env is not None else os.environ).get(ENV_CACHE_DIR)
    if raw:
        return Path(raw)
    for i, arg in enumerate(argv):
        if arg == "--compile-cache-dir" and i + 1 < len(argv):
            return Path(argv[i + 1])
        if arg.startswith("--compile-cache-dir="):
            return Path(arg.split("=", 1)[1])
    return DEFAULT_CACHE_DIR


def strip_elastic_args(argv: Sequence[str]) -> List[str]:
    """Remove every ``--elastic*`` flag (supervisor AND worker forms)
    from an argv list — the base command the supervisor re-issues per
    worker with fresh worker flags appended."""
    out: List[str] = []
    skip = False
    for arg in argv:
        if skip:
            skip = False
            continue
        if arg.startswith("--elastic"):
            if "=" not in arg:
                skip = True  # consume the flag's value token too
            continue
        out.append(arg)
    return out


# Per-worker output paths: two workers writing one JSONL interleave
# garbage, so the supervisor suffixes these flags' values with .w<slot>.
_PER_WORKER_PATH_FLAGS = ("--metrics-jsonl", "--telemetry-jsonl",
                          "--postmortem", "--tensorboard-dir", "--plot",
                          "--profile-trace-dir")


def _suffix_path(value: str, slot: int) -> str:
    """``loss.png -> loss.w1.png`` — the slot tag goes BEFORE the
    extension so consumers that infer format from the suffix
    (matplotlib's savefig, .jsonl tooling) keep working."""
    p = Path(value)
    return str(p.with_name(f"{p.stem}.w{slot}{p.suffix}")) if p.suffix \
        else f"{value}.w{slot}"


def rewrite_worker_paths(argv: Sequence[str], slot: int) -> List[str]:
    out = list(argv)
    for i, arg in enumerate(out):
        if arg in _PER_WORKER_PATH_FLAGS and i + 1 < len(out):
            out[i + 1] = _suffix_path(out[i + 1], slot)
        else:
            for flag in _PER_WORKER_PATH_FLAGS:
                prefix = flag + "="
                if arg.startswith(prefix):
                    out[i] = prefix + _suffix_path(
                        arg[len(prefix):], slot)
    return out


@dataclasses.dataclass
class _Worker:
    slot: int
    generation: int
    proc: subprocess.Popen
    log_path: Path
    log_fh: Any
    spawned_at: float = dataclasses.field(default_factory=time.monotonic)


class ElasticSupervisor:
    """Spawn N worker processes of one training command, keep them
    alive, and re-form the cluster when one dies or rejoins.

    The supervisor is deliberately policy-free about WHY a worker died —
    SIGKILL from a preemption, an OOM, a hung process past the heartbeat
    deadline all look the same: the membership generation bumps, the old
    generation's collectives break, survivors yield/fail out cleanly,
    and a smaller generation respawns from the last verified checkpoint.
    ``rejoin_s`` > 0 scales back up to the full worker count that many
    seconds after a loss, through the same graceful yield path (zero
    lost steps: the primary checkpoints at the yield boundary).
    """

    def __init__(self, worker_argv: Sequence[str], *, num_workers: int,
                 rendezvous: str | Path, checkpoint_dir: str | Path,
                 backend: str = "host",
                 module: str = "pytorch_vit_paper_replication_tpu.train",
                 python: str = sys.executable,
                 heartbeat_s: float = 1.0, timeout_s: float = 15.0,
                 rejoin_s: float = 0.0, local_devices: int = 0,
                 max_reforms: int = 32, grace_s: float = 30.0,
                 startup_timeout_s: float = 180.0,
                 env: Optional[dict] = None, registry=None,
                 verbose: bool = True):
        self.worker_argv = strip_elastic_args(worker_argv)
        self.num_workers = int(num_workers)
        self.rendezvous = Path(rendezvous)
        self.checkpoint_dir = Path(checkpoint_dir)
        self.backend = backend
        self.module = module
        self.python = python
        self.heartbeat_s = float(heartbeat_s)
        self.timeout_s = float(timeout_s)
        self.rejoin_s = float(rejoin_s)
        self.local_devices = int(local_devices)
        self.max_reforms = int(max_reforms)
        self.grace_s = float(grace_s)
        # A worker that hangs BEFORE its first heartbeat of the
        # generation (stuck import, a wedged coordinator connect) has
        # no per-generation staleness to read — this is its deadline
        # from spawn. Generous: it covers interpreter + jax import +
        # the pack open, which legitimately take tens of seconds.
        self.startup_timeout_s = float(startup_timeout_s)
        self._env = env
        if registry is None:
            from ..telemetry import get_registry
            registry = get_registry()
        self._registry = registry
        self.verbose = verbose
        self._server: Optional[AllReduceServer] = None
        self._coordinator: Optional[str] = None
        self._workers: List[_Worker] = []
        self._generation = 0
        self._interrupted = False  # set by the signal handler (GIL-atomic)
        self.reform_log: List[dict] = []
        # Crash-loop breaker state: consecutive LOSS reforms whose
        # restore step did not advance, and the cache root to
        # quarantine when the loop points at poisoned compile-cache
        # entries (see _maybe_quarantine_cache).
        self.quarantine_after = 3
        self._stuck_restores = 0
        self._last_loss_restore_step: Optional[int] = None
        self._cache_dir = worker_cache_dir(self.worker_argv,
                                           self._env)

    # ------------------------------------------------------- plumbing
    def _log(self, msg: str) -> None:
        if self.verbose:
            print(f"[elastic] {msg}", flush=True)

    def _worker_env(self) -> dict:
        env = dict(self._env if self._env is not None else os.environ)
        if self.local_devices > 0:
            # CPU-cluster emulation: each worker gets its own virtual
            # device split (the multihost-test recipe); a worker must
            # not inherit the parent's device-count flag.
            env["JAX_PLATFORMS"] = "cpu"
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f]
            flags.append("--xla_force_host_platform_device_count="
                         f"{self.local_devices}")
            env["XLA_FLAGS"] = " ".join(flags).strip()
        return env

    def _spawn(self, slot: int, generation: int,
               process_count: int) -> _Worker:
        argv = rewrite_worker_paths(self.worker_argv, slot)
        cmd = [self.python, "-m", self.module, *argv,
               "--elastic-worker-id", str(slot),
               "--elastic-process-count", str(process_count),
               "--elastic-generation", str(generation),
               "--elastic-rendezvous", str(self.rendezvous),
               "--elastic-backend", self.backend,
               "--elastic-heartbeat-s", str(self.heartbeat_s)]
        if self._server is not None:
            cmd += ["--elastic-collective", self._server.address]
        elif self.backend == "jax":
            # The jax backend reuses the same flag as the coordinator
            # address for jax.distributed.initialize.
            cmd += ["--elastic-collective", self._coordinator]
        log_dir = self.rendezvous / "logs"
        log_dir.mkdir(parents=True, exist_ok=True)
        log_path = log_dir / f"g{generation}_w{slot}.log"
        fh = open(log_path, "ab")
        proc = subprocess.Popen(cmd, stdout=fh, stderr=subprocess.STDOUT,
                                env=self._worker_env())
        self._log(f"gen {generation}: spawned worker {slot}/"
                  f"{process_count} pid {proc.pid} -> {log_path.name}")
        return _Worker(slot, generation, proc, log_path, fh)

    def _pick_coordinator(self) -> str:
        """A fresh 127.0.0.1 port for a jax-backend generation's
        ``jax.distributed`` coordinator (worker 0 binds it). Local
        processes only — this supervisor spawns on ONE host; remote
        spawn on a real pod is the cluster manager's job (ROADMAP 3)."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return f"127.0.0.1:{s.getsockname()[1]}"

    def _spawn_generation(self, generation: int,
                          process_count: int) -> None:
        if self._server is not None:
            self._server.set_generation(generation, process_count)
        if self.backend == "jax":
            # Every generation gets a fresh coordinator address: the
            # old cluster's port may linger in TIME_WAIT, and workers
            # re-init against the NEW address.
            self._coordinator = self._pick_coordinator()
        write_membership(self.rendezvous, generation=generation,
                         process_count=process_count)
        self._workers = [self._spawn(slot, generation, process_count)
                         for slot in range(process_count)]
        self._registry.gauge("elastic_generation", generation)
        self._registry.gauge("elastic_workers", process_count)

    def _kill_all(self, sig: int = signal.SIGKILL) -> None:
        for w in self._workers:
            if w.proc.poll() is None:
                try:
                    w.proc.send_signal(sig)
                except OSError:
                    pass

    def _reap(self, worker: _Worker) -> None:
        try:
            worker.log_fh.close()
        except OSError:
            pass

    # -------------------------------------------------------- reform
    def _drain_and_respawn(self, *, target_pc: int, reason: str,
                           graceful: bool, detect_t: float) -> None:
        """One re-formation: announce generation g+1, release the old
        generation, wait it out, respawn at the new size."""
        old_gen = self._generation
        self._generation += 1
        write_membership(self.rendezvous, generation=self._generation,
                         process_count=target_pc, reason=reason)
        self._log(f"reform -> gen {self._generation} pc {target_pc} "
                  f"({reason})")
        if self._server is not None and not graceful:
            self._server.break_generation(old_gen, reason)
        # Wait for the old generation to exit. Graceful reforms get one
        # step's worth of patience before the collective is broken too:
        # a worker blocked in an allreduce its yielded peer will never
        # join would otherwise hang to its client timeout.
        deadline = time.monotonic() + self.grace_s
        broke = not graceful
        while any(w.proc.poll() is None for w in self._workers):
            alive = [w for w in self._workers if w.proc.poll() is None]
            exited = len(self._workers) - len(alive)
            if not broke and (exited > 0
                             or time.monotonic() > deadline
                             - self.grace_s + 4 * self.heartbeat_s):
                if self._server is not None:
                    self._server.break_generation(old_gen, reason)
                broke = True
            if time.monotonic() > deadline:
                self._log(f"gen {old_gen}: {len(alive)} straggler(s) "
                          "past grace — killing")
                self._kill_all(signal.SIGTERM)
                time.sleep(1.0)
                self._kill_all(signal.SIGKILL)
            time.sleep(0.1)
        max_seen = 0
        for hb in read_heartbeats(self.rendezvous).values():
            if int(hb.get("generation", -1)) == old_gen:
                max_seen = max(max_seen, int(hb.get("step", 0)))
        for w in self._workers:
            self._reap(w)
        ckpt_step = latest_checkpoint_step(self.checkpoint_dir) or 0
        lost = max(0, max_seen - ckpt_step)
        self._registry.count("elastic_reforms_total")
        self._registry.count("elastic_lost_steps_total", lost)
        if not graceful:
            self._maybe_quarantine_cache(ckpt_step)
        self._spawn_generation(self._generation, target_pc)
        took = time.monotonic() - detect_t
        self._registry.gauge("elastic_last_recovery_s", round(took, 3))
        self.reform_log.append({
            "generation": self._generation, "process_count": target_pc,
            "reason": reason, "graceful": graceful,
            "checkpoint_step": ckpt_step, "max_step_seen": max_seen,
            "lost_steps": lost, "respawn_s": round(took, 3),
            "time": time.time()})
        self._log(f"gen {self._generation}: respawned pc {target_pc}, "
                  f"restore step {ckpt_step}, lost {lost} step(s), "
                  f"reform took {took:.1f}s")

    def _maybe_quarantine_cache(self, restore_step: int) -> None:
        """Break compile-cache crash loops.

        A torn persistent-cache entry (a worker SIGKILLed mid-write
        before the atomic-put guard existed, shared-filesystem
        corruption, …) segfaults every process that deserializes it —
        so each respawned generation dies instantly at the SAME restore
        step and the job churns forever. Detector: `quarantine_after`
        consecutive worker-LOSS reforms whose restore step never
        advanced. Response: move the compile-cache root aside
        (`<dir>.quarantined.<n>`, kept for forensics) so the next
        generation recompiles cleanly — one cold start instead of an
        infinite crash loop."""
        if restore_step == self._last_loss_restore_step:
            self._stuck_restores += 1
        else:
            self._stuck_restores = 0
            self._last_loss_restore_step = restore_step
        if (self._stuck_restores < self.quarantine_after
                or not self._cache_dir.exists()):
            return
        dest = self._cache_dir.with_name(
            f"{self._cache_dir.name}.quarantined.{self._generation}")
        try:
            os.replace(self._cache_dir, dest)
        except OSError as e:
            self._log(f"cache quarantine failed: {e}")
            return
        self._stuck_restores = 0
        self._registry.count("elastic_cache_quarantines_total")
        self._log(
            f"{self.quarantine_after} consecutive losses stuck at "
            f"restore step {restore_step} — quarantined the compile "
            f"cache to {dest.name} (a torn cache entry segfaults every "
            f"deserializing process; next generation recompiles)")

    # ------------------------------------------------------------ run
    def run(self) -> dict:
        """Supervise to completion. Returns the summary dict (also
        written to ``<rendezvous>/supervisor.json``)."""
        t_start = time.monotonic()
        self.rendezvous.mkdir(parents=True, exist_ok=True)
        if self.backend == "host":
            self._server = AllReduceServer()
        prev_handlers = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev_handlers[sig] = signal.signal(
                sig, lambda *_: setattr(self, "_interrupted", True))
        result = "failed"
        recoveries = 0
        rejoin_at: Optional[float] = None
        try:
            self._spawn_generation(0, self.num_workers)
            while True:
                if self._interrupted:
                    self._log("interrupted — killing workers")
                    self._kill_all(signal.SIGTERM)
                    time.sleep(1.0)
                    self._kill_all(signal.SIGKILL)
                    result = "interrupted"
                    break
                codes = [w.proc.poll() for w in self._workers]
                if all(c == 0 for c in codes):
                    result = "completed"
                    break
                # A worker loss: unexpected exit code, or a live process
                # whose heartbeat went stale past the deadline (hung).
                # EXIT_YIELD/EXIT_COLLECTIVE are protocol, not losses:
                # a worker that noticed a dying peer before this poll
                # did (its collective broke first) already stepped
                # aside cleanly and is a SURVIVOR to respawn — without
                # this, the kill-then-fast-exit race respawned at full
                # size instead of shrinking to the survivors.
                now = time.time()
                beats = read_heartbeats(self.rendezvous)
                dead = []
                for w, c in zip(self._workers, codes):
                    if c is not None and c not in (0, EXIT_YIELD,
                                                   EXIT_COLLECTIVE):
                        dead.append((w, f"exit {c}"))
                        continue
                    if c is None:
                        hb = beats.get(w.slot)
                        fresh = (hb is not None
                                 and int(hb.get("generation", -1))
                                 == w.generation)
                        if fresh and now - float(hb.get("time", 0)) \
                                > self.timeout_s:
                            self._registry.count(
                                "elastic_heartbeat_misses_total")
                            dead.append((w, "heartbeat stale"))
                        elif not fresh and (time.monotonic()
                                            - w.spawned_at
                                            > self.startup_timeout_s):
                            # Hung before its first heartbeat of this
                            # generation: no staleness to read, so the
                            # deadline runs from spawn.
                            self._registry.count(
                                "elastic_heartbeat_misses_total")
                            dead.append((w, "no heartbeat since spawn"))
                protocol_exits = [
                    w for w, c in zip(self._workers, codes)
                    if c in (EXIT_YIELD, EXIT_COLLECTIVE)]
                if dead or protocol_exits:
                    if len(self.reform_log) >= self.max_reforms:
                        self._log("max_reforms exceeded — giving up")
                        self._kill_all()
                        result = "failed"
                        break
                    detect_t = time.monotonic()
                    for w, why in dead:
                        self._log(f"worker {w.slot} lost ({why})")
                        if w.proc.poll() is None:
                            w.proc.kill()
                    # Survivors = still-running workers plus the ones
                    # that already yielded/failed out on the broken
                    # collective — both resume in the next generation.
                    dead_slots = {d.slot for d, _ in dead}
                    survivors = sum(
                        1 for w, c in zip(self._workers, codes)
                        if (c is None or c in (EXIT_YIELD,
                                               EXIT_COLLECTIVE))
                        and w.slot not in dead_slots)
                    target = max(1, survivors) if survivors \
                        else len(self._workers)
                    recoveries += 1
                    self._registry.count("elastic_recoveries_total")
                    reason = (f"worker lost ({dead[0][1]})" if dead
                              else "collective broke under a worker")
                    self._drain_and_respawn(
                        target_pc=target, reason=reason,
                        graceful=False, detect_t=detect_t)
                    if self.rejoin_s > 0 and target < self.num_workers:
                        rejoin_at = time.monotonic() + self.rejoin_s
                    continue
                if (rejoin_at is not None
                        and time.monotonic() >= rejoin_at
                        and all(c is None for c in codes)):
                    rejoin_at = None
                    self._drain_and_respawn(
                        target_pc=self.num_workers, reason="rejoin",
                        graceful=True, detect_t=time.monotonic())
                    continue
                time.sleep(min(0.2, self.heartbeat_s / 2))
        finally:
            for sig, h in prev_handlers.items():
                signal.signal(sig, h)
            self._kill_all()
            for w in self._workers:
                self._reap(w)
            if self._server is not None:
                self._server.close()
        summary = {
            "result": result,
            "num_workers": self.num_workers,
            "final_process_count": len(self._workers),
            "generations": self._generation + 1,
            "recoveries": recoveries,
            "reforms": self.reform_log,
            "lost_steps_total": sum(r["lost_steps"]
                                    for r in self.reform_log),
            "wall_s": round(time.monotonic() - t_start, 3),
            "telemetry": self._registry.snapshot(),
        }
        atomic_write_json(self.rendezvous / SUPERVISOR_NAME, summary,
                          indent=2)
        self._log(f"{result}: {recoveries} recover(ies), "
                  f"{self._generation} reform(s), "
                  f"{summary['lost_steps_total']} lost step(s), "
                  f"{summary['wall_s']:.1f}s")
        return summary
