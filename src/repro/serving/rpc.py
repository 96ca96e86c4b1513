"""Process-boundary serving engines: ``EngineWorker`` + ``EngineProxy``.

The ``Router`` scales serving across engines, but in-process engines
still share one Python interpreter: a prefill storm on engine 0 steals
wall-clock from engine 1's decode ticks (the GIL and the single
dispatch thread serialize them).  This module puts each engine in its
own **worker process** — one ``Scheduler`` per process, each owning its
own jax runtime — and fronts it with an ``EngineProxy`` that speaks the
full engine surface the router uses, over a length-prefixed frame
protocol (``repro.serving.wire``) on the worker's stdin/stdout pipes.

Protocol (all frames are ``wire``-encoded):

  * proxy → worker: one **init** frame (arch config, params seed or
    host-materialized params, engine kwargs, optional mesh shape), then
    a stream of ``[op, payload]`` frames;
  * worker → proxy: one reply per frame —
    ``{"ok", "result", "updates", "status"}``.  ``updates`` streams the
    mutable-progress slice of every live request (output tokens, state,
    timing stamps) so the **caller's own ``Request`` objects stay
    live** — the proxy keeps a mirror of every submitted request and
    applies updates to the original objects, exactly like an in-process
    engine mutating them.  ``status`` snapshots the narrow surface the
    router reads between calls (``load``, ``free_slots``, ``handoffs``,
    …) so reading a proxy property never blocks on a round trip.

Pipelined stepping: ``step_begin`` issues a tick without waiting and
``step_drain(block=...)`` collects the reply when it lands — at most
one step is ever in flight, every other op flushes it first.  The
router uses this to let a decode worker tick at its own pace while a
prefill worker chews a long prompt (the disaggregation win: two
processes really do run concurrently).

Worker death: EOF / broken pipe on the channel raises ``WorkerDied``;
the proxy marks itself dead and ``recover_queued`` hands back the
still-queued mirror requests (re-homeable — their prompts live in the
caller) and marks requests whose state lived in the dead process as
``"failed"``.

Weights cross the boundary as a **seed** when possible
(``params_seed`` / ``draft_params_seed`` → the worker rebuilds
``lm.init_lm(PRNGKey(seed), cfg)``, deterministic across processes) and
as host numpy otherwise.  No timeouts are imposed on replies — a first
step may sit behind minutes of XLA compilation; death is detected by
EOF, not silence.

One process per chip: a TPU chip belongs to the one process that opened
it, so a launcher that serves through workers never initialises JAX's
backend itself.  ``worker_chips`` asks a short-lived child process what
the host has, and ``EngineProxy(chip=i)`` limits its worker to chip
``i`` through libtpu's environment before the worker imports JAX.
"""
from __future__ import annotations

import os
import selectors
import socket
import subprocess
import sys
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.serving import wire

# ops the worker understands; everything the Router touches on an engine
_OPS = ("submit", "step", "pause", "resume", "touch", "withdraw",
        "readmit", "withdraw_swapped", "readmit_swapped",
        "withdraw_handoff", "flush_swaps", "metrics", "reset_metrics",
        "shutdown")

_EXC: Dict[str, type] = {
    "ValueError": ValueError, "KeyError": KeyError,
    "IndexError": IndexError, "TypeError": TypeError,
    "RuntimeError": RuntimeError,
}


class WorkerDied(RuntimeError):
    """The engine worker process is gone (EOF/broken pipe mid-call)."""


_PROBE = "import jax; print(jax.default_backend(), jax.device_count())"


def worker_chips(n_workers: int, python: str = sys.executable
                 ) -> List[Optional[int]]:
    """Chip index for each of ``n_workers`` one-chip workers (``None``
    each off a TPU, where workers share the host's CPU backend).

    The platform and chip count come from a child process that exits
    before any worker starts, so the caller never holds a chip.  More
    workers than chips is a ``ValueError``.
    """
    out = subprocess.run([python, "-c", _PROBE], check=True,
                         capture_output=True, text=True).stdout
    platform, count = out.split()[-2:]
    if platform != "tpu":
        return [None] * n_workers
    if n_workers > int(count):
        raise ValueError(
            f"{n_workers} engine workers need one TPU chip each, but this "
            f"host has {count}")
    return list(range(n_workers))


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


def _chip_env(chip: int) -> Dict[str, str]:
    """libtpu settings that give a process chip ``chip`` alone, as a
    one-chip slice of its own (and never a CPU fallback)."""
    port = _free_port()
    return {"JAX_PLATFORMS": "tpu",
            "TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}"}


def _hostify(tree):
    """Materialize a (possibly device-resident) pytree as host numpy so
    the wire codec frames every leaf bitwise instead of pickling it."""
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


# ======================================================================
# worker side
# ======================================================================
def _status(eng) -> Dict[str, Any]:
    return {
        "load": eng.load,
        "queue_len": eng.queue_len,
        "free_slots": eng.free_slots,
        "staging_len": eng.staging_len,
        "resume_len": eng.resume_len,
        "idle_capacity": eng.idle_capacity,
        "handoffs": eng.handoffs,
    }


class EngineWorker:
    """Hosts one ``Scheduler`` and serves the frame protocol on a pair
    of binary streams.  Run as ``python -m repro.serving.rpc`` (stdin /
    stdout pipes — stdout is reserved for frames; anything the engine
    prints goes to stderr)."""

    def __init__(self, inp, out):
        self.inp = inp
        self.out = out
        self.eng = None
        self.reqs: Dict[int, Any] = {}      # rid -> live worker-side Request

    # ------------------------------------------------------------ setup
    def _build(self, init: Dict[str, Any]):
        import jax
        from repro.serving.scheduler import Scheduler

        from repro.models import lm

        cfg = init["cfg"]
        if init.get("params_seed") is not None:
            params = lm.init_lm(jax.random.PRNGKey(init["params_seed"]),
                                cfg)
        else:
            params = init["params"]
        kwargs = dict(init.get("kwargs") or {})
        if init.get("draft_params_seed") is not None:
            kwargs["draft_params"] = lm.init_lm(
                jax.random.PRNGKey(init["draft_params_seed"]),
                kwargs["draft_cfg"])
        mesh_shape = init.get("mesh_shape")
        if mesh_shape is not None:
            from repro.launch.mesh import make_mesh
            axes = tuple(init.get("mesh_axes") or ("data", "model"))
            kwargs["mesh"] = make_mesh(tuple(mesh_shape), axes)
        self.eng = Scheduler(cfg, params, **kwargs)
        return {"max_len": self.eng.max_len, "role": self.eng.role,
                "max_slots": self.eng.max_slots}

    # --------------------------------------------------------- dispatch
    def _dispatch(self, op: str, payload) -> Any:
        eng = self.eng
        if op == "submit":
            req = wire.decode_request(payload)
            eng.submit(req)
            self.reqs[req.rid] = req
            return None
        if op == "step":
            eng.step()
            return None
        if op == "pause":
            eng.pause(payload)
            return None
        if op == "resume":
            eng.resume(payload)
            return None
        if op == "touch":
            eng.touch(payload)
            return None
        if op == "withdraw":
            req = eng.withdraw(oldest=bool(payload))
            if req is None:
                return None
            self.reqs.pop(req.rid, None)
            return wire.request_update(req)
        if op == "readmit":
            req = wire.decode_request(payload)
            eng.readmit(req)
            self.reqs[req.rid] = req
            return None
        if op in ("withdraw_swapped", "withdraw_handoff"):
            rec = (eng.withdraw_swapped() if op == "withdraw_swapped"
                   else eng.withdraw_handoff())
            if rec is None:
                return None
            self.reqs.pop(rec.req.rid, None)
            return wire.encode_swap_record(rec)
        if op == "readmit_swapped":
            rec = wire.decode_swap_record(payload)
            eng.readmit_swapped(rec)
            self.reqs[rec.req.rid] = rec.req
            return None
        if op == "flush_swaps":
            eng.flush_swaps()
            return None
        if op == "metrics":
            return eng.metrics()
        if op == "reset_metrics":
            eng.reset_metrics()
            return None
        if op == "shutdown":
            return None
        raise ValueError(f"rpc: unknown op {op!r}")

    def _updates(self) -> List[Dict[str, Any]]:
        ups = []
        for rid, req in list(self.reqs.items()):
            ups.append(wire.request_update(req))
            if req.done:        # final update sent — the proxy's mirror
                del self.reqs[rid]      # keeps the finished object
        return ups

    def _reply(self, ok: bool, result=None, err: Optional[Tuple] = None):
        msg = {"ok": ok, "result": result,
               "updates": self._updates() if self.eng is not None else [],
               "status": _status(self.eng) if self.eng is not None
               else None}
        if err is not None:
            msg["err"], msg["msg"] = err
        wire.write_frame(self.out, wire.encode(msg))

    # ------------------------------------------------------------- loop
    def serve(self) -> int:
        try:
            init = wire.decode(wire.read_frame(self.inp))
        except EOFError:
            return 0
        try:
            info = self._build(init)
        except Exception as e:          # init failure is fatal
            self._reply(False, err=(type(e).__name__, str(e)))
            return 1
        self._reply(True, result=info)
        while True:
            try:
                frame = wire.read_frame(self.inp)
            except EOFError:            # proxy closed the pipe: done
                return 0
            op, payload = wire.decode(frame)
            try:
                result = self._dispatch(op, payload)
            except Exception as e:
                self._reply(False, err=(type(e).__name__, str(e)))
            else:
                self._reply(True, result=result)
            if op == "shutdown":
                return 0


# ======================================================================
# proxy side
# ======================================================================
class EngineProxy:
    """Router-facing handle on an ``EngineWorker`` subprocess.  Speaks
    the in-process engine surface: ``submit``/``step``/``pause``/
    ``resume``/``touch``/``withdraw*``/``readmit*``/``metrics``/… plus
    the pipelined ``step_begin``/``step_drain`` pair the router uses to
    tick workers concurrently.  Constructor args mirror ``Scheduler``
    — pass ``params_seed`` instead of params when the weights are a
    deterministic init (cheap to ship, bitwise-identical on rebuild), and
    ``draft_params_seed`` likewise for a speculative draft.  ``chip``
    (from ``worker_chips``) limits the worker to that one TPU chip."""

    def __init__(self, cfg, params=None, *, params_seed: Optional[int] = None,
                 draft_params_seed: Optional[int] = None,
                 chip: Optional[int] = None,
                 mesh_shape=None, mesh_axes=None,
                 python: str = sys.executable, **engine_kwargs):
        if (params is None) == (params_seed is None):
            raise ValueError("EngineProxy: pass exactly one of params / "
                             "params_seed")
        self.cfg = cfg
        self.role = engine_kwargs.get("role", "both")
        self.dead = False
        self._reqs: Dict[int, Any] = {}     # mirror: rid -> caller's Request
        self._status: Dict[str, Any] = {
            "load": 0, "queue_len": 0, "free_slots": 0, "staging_len": 0,
            "resume_len": 0, "idle_capacity": 0, "handoffs": 0}
        self._inflight_step = False
        if "draft_params" in engine_kwargs \
                and engine_kwargs["draft_params"] is not None:
            engine_kwargs["draft_params"] = _hostify(
                engine_kwargs["draft_params"])
        env = None if chip is None else {**os.environ, **_chip_env(chip)}
        self.proc = subprocess.Popen(
            [python, "-m", "repro.serving.rpc"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        self._sel = selectors.DefaultSelector()
        self._sel.register(self.proc.stdout, selectors.EVENT_READ)
        init = {"cfg": cfg,
                "params": None if params is None else _hostify(params),
                "params_seed": params_seed,
                "draft_params_seed": draft_params_seed,
                "kwargs": engine_kwargs,
                "mesh_shape": (tuple(mesh_shape)
                               if mesh_shape is not None else None),
                "mesh_axes": (tuple(mesh_axes)
                              if mesh_axes is not None else None)}
        self._write(wire.encode(init))
        info = self._read_reply()           # blocks through engine build
        self.max_len = info["max_len"]
        self.max_slots = info["max_slots"]
        self.role = info["role"]

    # ---------------------------------------------------------- channel
    def _write(self, payload: bytes):
        try:
            wire.write_frame(self.proc.stdin, payload)
        except (BrokenPipeError, OSError) as e:
            self._die(e)

    def _read_reply(self):
        try:
            reply = wire.decode(wire.read_frame(self.proc.stdout))
        except (EOFError, OSError) as e:
            self._die(e)
        if reply.get("status") is not None:
            self._status = reply["status"]
        for u in reply.get("updates") or ():
            req = self._reqs.get(u["rid"])
            if req is not None:
                wire.apply_request_update(req, u)
        if not reply["ok"]:
            exc = _EXC.get(reply.get("err", ""), RuntimeError)
            raise exc(f"[worker] {reply.get('msg', '')}")
        return reply["result"]

    def _die(self, cause) -> "NoReturn":
        self.dead = True
        self._inflight_step = False
        try:
            self.proc.kill()
        except OSError:
            pass
        raise WorkerDied(f"engine worker pid {self.proc.pid} died: "
                         f"{cause}") from cause

    def _call(self, op: str, payload=None):
        if self.dead:
            raise WorkerDied(f"engine worker pid {self.proc.pid} is dead")
        self.step_drain(block=True)         # at most one frame in flight
        self._write(wire.encode([op, payload]))
        return self._read_reply()

    # ------------------------------------------------- pipelined ticking
    def step_begin(self):
        """Issue one tick without waiting for it.  No-op if a tick is
        already in flight — the worker paces itself."""
        if self.dead:
            raise WorkerDied(f"engine worker pid {self.proc.pid} is dead")
        if self._inflight_step:
            return
        self._write(wire.encode(["step", None]))
        self._inflight_step = True

    def step_drain(self, *, block: bool) -> bool:
        """Collect the in-flight tick's reply if there is one.  With
        ``block=False`` returns False when the worker hasn't answered
        yet; with ``block=True`` waits for it.  Returns True if a reply
        was consumed."""
        if not self._inflight_step:
            return False
        if not block and not self._sel.select(timeout=0):
            return False
        self._inflight_step = False
        self._read_reply()
        return True

    def step(self):
        self.step_begin()
        self.step_drain(block=True)

    # ------------------------------------------------------- engine surface
    def submit(self, req):
        self._reqs[req.rid] = req
        try:
            self._call("submit", wire.encode_request(req))
        except Exception:
            if not req.done and req.state in ("new", "failed"):
                self._reqs.pop(req.rid, None)
            raise

    def withdraw(self, *, oldest: bool = False):
        u = self._call("withdraw", oldest)
        if u is None:
            return None
        req = self._reqs.pop(u["rid"])
        wire.apply_request_update(req, u)
        return req

    def readmit(self, req):
        self._reqs[req.rid] = req
        self._call("readmit", wire.encode_request(req))

    def pause(self, rid: int):
        self._call("pause", rid)
        return self._reqs[rid]

    def resume(self, rid: int):
        self._call("resume", rid)
        return self._reqs[rid]

    def touch(self, rid: int):
        self._call("touch", rid)

    def _withdraw_record(self, op: str):
        raw = self._call(op)
        if raw is None:
            return None
        rec = wire.decode_swap_record(raw)
        # hand back the CALLER'S request object, not the wire copy: the
        # router re-homes records between engines while clients keep
        # polling the object they submitted
        mine = self._reqs.pop(rec.req.rid, None)
        if mine is not None:
            wire.apply_request_update(mine, wire.request_update(rec.req))
            rec.req = mine
        return rec

    def withdraw_swapped(self):
        return self._withdraw_record("withdraw_swapped")

    def withdraw_handoff(self):
        return self._withdraw_record("withdraw_handoff")

    def readmit_swapped(self, rec):
        self._reqs[rec.req.rid] = rec.req
        self._call("readmit_swapped", wire.encode_swap_record(rec))

    def flush_swaps(self):
        self._call("flush_swaps")

    def metrics(self) -> Dict[str, Any]:
        return self._call("metrics")

    def reset_metrics(self):
        self._call("reset_metrics")

    # ------------------------------------------------- router narrow surface
    @property
    def load(self) -> int:
        return self._status["load"]

    @property
    def queue_len(self) -> int:
        return self._status["queue_len"]

    @property
    def free_slots(self) -> int:
        return self._status["free_slots"]

    @property
    def staging_len(self) -> int:
        return self._status["staging_len"]

    @property
    def resume_len(self) -> int:
        return self._status["resume_len"]

    @property
    def idle_capacity(self) -> int:
        return self._status["idle_capacity"]

    @property
    def handoffs(self) -> int:
        return self._status["handoffs"]

    def owns(self, rid: int) -> bool:
        req = self._reqs.get(rid)
        return req is not None and not req.done

    def done_requests(self):
        return [r for r in self._reqs.values() if r.done]

    # ---------------------------------------------------- death recovery
    def recover_queued(self):
        """After the worker died: split the mirror into requests that
        never left the queue (returned for re-homing — their prompts
        live caller-side) and requests whose device/host state died with
        the process (marked ``"failed"``)."""
        queued, lost = [], []
        for req in self._reqs.values():
            if req.done:
                continue
            if req.state in ("new", "queued"):
                queued.append(req)
            else:
                req.state = "failed"
                lost.append(req)
        for req in queued:      # re-homed requests leave this mirror so
            self._reqs.pop(req.rid, None)   # only the new owner reports
        return queued, lost                 # them via done_requests()

    # ----------------------------------------------------------- teardown
    def shutdown(self):
        """Graceful stop: drain any in-flight tick, send shutdown, reap
        the process.  Safe to call twice / after death."""
        if not self.dead:
            try:
                self._call("shutdown")
            except WorkerDied:
                pass
        self.dead = True
        for stream in (self.proc.stdin, self.proc.stdout):
            try:
                stream.close()
            except OSError:
                pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()

    close = shutdown

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()

    def __del__(self):
        try:
            if self.proc.poll() is None:
                self.proc.kill()
        except Exception:
            pass


def main() -> int:
    # stdout carries frames; rebind print()-style output to stderr so a
    # stray print inside jax/engine code can't corrupt the protocol
    out = sys.stdout.buffer
    sys.stdout = sys.stderr
    return EngineWorker(sys.stdin.buffer, out).serve()


if __name__ == "__main__":
    sys.exit(main())
