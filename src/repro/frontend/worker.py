"""Worker replicas behind the engine-API boundary.

Three layers share one implementation of the boundary
(``frontend.protocol``):

  * ``EngineHost`` — wraps one ``repro.engine.Engine`` with the rid-keyed
    add/step/preempt surface. All device state lives here.
  * ``LocalReplica`` — an ``EngineHost`` in the calling process, for
    tests and benchmarks that want orchestrator semantics without
    process overhead (and for ``--workers 0``).
  * ``ProcReplica`` — an ``EngineHost`` in a **spawned child process**
    driven over a ``multiprocessing`` pipe (``worker_main`` is the child
    entry point). The child forces its own XLA host-device count from
    the plan *before* importing jax, so each worker owns exactly its
    replica's devices regardless of the parent's mesh; params are
    re-derived from the same init seed, so replicas hold bit-identical
    weights without shipping them.

``ProcReplica.step_send`` / ``step_recv`` are split so the orchestrator
can fan a step out to every worker and only then collect — the workers'
device steps genuinely overlap (separate processes, separate XLA
clients), which is where the 2-process > 1-process throughput at equal
device count comes from.

This module must stay importable without initialising jax: the child
imports it *before* setting XLA flags would be too late, so nothing at
module top level may touch jax (everything heavyweight is imported
inside functions).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from repro.frontend import protocol
from repro.frontend.protocol import ReplicaDead, StepResult


class EngineHost:
    """One engine behind the rid-keyed boundary surface."""

    def __init__(self, spec: Dict[str, Any]):
        from repro import obs
        from repro.configs import registry as arch_registry
        from repro.engine import Engine, EngineConfig
        from repro.models.factory import build_model
        from repro.plan import ExecutionPlan

        plan = ExecutionPlan.from_dict(spec["plan"])
        cfg = arch_registry.for_plan(plan)
        eng_kw = dict(spec.get("eng") or {})
        if spec.get("prefill_chunk"):
            eng_kw["prefill_chunk"] = spec["prefill_chunk"]
        self.registry = obs.Registry()
        self.tracer = obs.Tracer(enabled=bool(spec.get("trace")))
        model = build_model(cfg)
        self.engine = Engine(model, plan, EngineConfig(**eng_kw),
                             registry=self.registry, tracer=self.tracer,
                             seed=int(spec.get("init_seed", 0)))
        self._reported: set = set()

    # ---- boundary calls --------------------------------------------------
    def add(self, rid: int, req_wire: Dict[str, Any]) -> Optional[Dict]:
        req = protocol.request_from_wire(req_wire)
        rej = self.engine.add_request(req)
        return None if rej is None else protocol.rejection_to_wire(rej)

    def step(self) -> StepResult:
        emitted = [(protocol.rid_for(uid), tok)
                   for uid, tok in self.engine.step()]
        sched = self.engine.scheduler
        finished = [protocol.rid_for(uid) for uid in sched.finished
                    if uid not in self._reported]
        self._reported.update(protocol.uid_for(r) for r in finished)
        outstanding = sum(r.prompt_len + r.max_new_tokens
                          for r in sched.queue)
        outstanding += sum(
            s.req.prompt_len + s.req.max_new_tokens - len(s.out)
            for s in sched.active())
        return protocol.pack_step(
            emitted, finished,
            free_slots=sum(1 for s in sched.slots if s is None),
            queued=len(sched.queue), active=len(sched.active()),
            outstanding_tokens=outstanding)

    def preempt(self, rid: int) -> Optional[Dict[str, Any]]:
        resume = self.engine.preempt(protocol.uid_for(rid))
        return None if resume is None else protocol.request_to_wire(resume)

    def idle(self) -> bool:
        return self.engine.idle()

    def flush(self) -> None:
        self.engine.connector.flush()

    def metrics_text(self) -> str:
        return self.registry.render_prometheus()

    def trace_events(self) -> List[Dict[str, Any]]:
        return self.tracer.events()


def worker_main(conn, spec: Dict[str, Any]) -> None:
    """Child-process entry point: build the engine, serve the pipe.

    The XLA host-device count is forced from the plan **before** any jax
    import — the child inherits only a bare interpreter (spawn context),
    so this is the first and only backend configuration it sees."""
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               f"{int(spec['n_devices'])}")
    try:
        host = EngineHost(spec)
    except Exception as e:              # surface build failures, don't hang
        conn.send(("error", f"{type(e).__name__}: {e}"))
        conn.close()
        return
    conn.send(("ready", os.getpid()))
    while True:
        try:
            msg = conn.recv()
        except EOFError:
            break
        op, args = msg[0], msg[1:]
        try:
            if op == "add":
                conn.send(("rej", host.add(*args)))
            elif op == "step":
                conn.send(("step", host.step()))
            elif op == "preempt":
                conn.send(("req", host.preempt(*args)))
            elif op == "flush":
                host.flush()
                conn.send(("ok", None))
            elif op == "idle":
                conn.send(("bool", host.idle()))
            elif op == "metrics":
                conn.send(("text", host.metrics_text()))
            elif op == "trace":
                conn.send(("events", host.trace_events()))
            elif op == "shutdown":
                conn.send(("ok", None))
                break
            else:
                conn.send(("error", f"unknown op {op!r}"))
        except Exception as e:          # keep serving after a bad request
            conn.send(("error", f"{type(e).__name__}: {e}"))
    conn.close()


class LocalReplica:
    """The boundary surface over an in-process ``EngineHost``."""

    def __init__(self, index: int, spec: Dict[str, Any]):
        self.index = index
        self.host = EngineHost(spec)
        self.alive = True
        self.last: Optional[StepResult] = None
        self._pending = False

    def add(self, rid: int, req_wire: Dict[str, Any]) -> Optional[Dict]:
        return self.host.add(rid, req_wire)

    def step_send(self) -> None:
        self._pending = True

    def step_recv(self) -> StepResult:
        assert self._pending, "step_recv without step_send"
        self._pending = False
        self.last = self.host.step()
        return self.last

    def preempt(self, rid: int) -> Optional[Dict[str, Any]]:
        return self.host.preempt(rid)

    def idle(self) -> bool:
        return self.host.idle()

    def flush(self) -> None:
        self.host.flush()

    def metrics_text(self) -> str:
        return self.host.metrics_text()

    def trace_events(self) -> List[Dict[str, Any]]:
        return self.host.trace_events()

    def shutdown(self) -> None:
        self.alive = False

    def kill(self) -> None:
        self.alive = False


class ProcReplica:
    """The boundary surface over a spawned worker process."""

    def __init__(self, index: int, spec: Dict[str, Any], *,
                 start_timeout_s: float = 300.0):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self.index = index
        self.conn, child = ctx.Pipe()
        self.proc = ctx.Process(target=worker_main, args=(child, spec),
                                daemon=True)
        self.proc.start()
        child.close()
        self.alive = True
        self.last: Optional[StepResult] = None
        self._pending = False
        if not self.conn.poll(start_timeout_s):
            self.kill()
            raise ReplicaDead(index, "worker did not come up")
        try:
            tag, payload = self.conn.recv()
        except (EOFError, OSError) as e:
            self.kill()
            raise ReplicaDead(index, f"worker died during startup: {e}")
        if tag != "ready":
            self.kill()
            raise ReplicaDead(index, str(payload))
        self.pid = payload

    # ---- plumbing --------------------------------------------------------
    def _send(self, *msg) -> None:
        if not self.alive:
            raise ReplicaDead(self.index, "already dead")
        try:
            self.conn.send(msg)
        except (BrokenPipeError, OSError) as e:
            self.alive = False
            raise ReplicaDead(self.index, str(e))

    def _recv(self, expect: str):
        try:
            tag, payload = self.conn.recv()
        except (EOFError, OSError) as e:
            self.alive = False
            raise ReplicaDead(self.index, str(e))
        if tag == "error":
            raise RuntimeError(f"replica {self.index}: {payload}")
        if tag != expect:
            raise RuntimeError(
                f"replica {self.index}: expected {expect!r}, got {tag!r}")
        return payload

    def _rpc(self, expect: str, *msg):
        self._send(*msg)
        return self._recv(expect)

    # ---- boundary calls --------------------------------------------------
    def add(self, rid: int, req_wire: Dict[str, Any]) -> Optional[Dict]:
        return self._rpc("rej", "add", rid, req_wire)

    def step_send(self) -> None:
        self._send("step")
        self._pending = True

    def step_recv(self) -> StepResult:
        assert self._pending, "step_recv without step_send"
        self._pending = False
        self.last = self._recv("step")
        return self.last

    def preempt(self, rid: int) -> Optional[Dict[str, Any]]:
        return self._rpc("req", "preempt", rid)

    def idle(self) -> bool:
        return self._rpc("bool", "idle")

    def flush(self) -> None:
        self._rpc("ok", "flush")

    def metrics_text(self) -> str:
        return self._rpc("text", "metrics")

    def trace_events(self) -> List[Dict[str, Any]]:
        return self._rpc("events", "trace")

    def shutdown(self, timeout_s: float = 30.0) -> None:
        if self.alive:
            try:
                self._rpc("ok", "shutdown")
            except (ReplicaDead, RuntimeError):
                pass
            self.alive = False
        self.proc.join(timeout_s)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(5.0)

    def kill(self) -> None:
        """Hard-kill the worker process (replica-death testing). The
        client side stays nominally alive: the next RPC hits the broken
        pipe and raises ReplicaDead, exactly as a real crash surfaces."""
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(10.0)
