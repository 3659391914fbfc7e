"""Worker process entry point: executes pushed tasks and hosts actors.

Capability parity with the reference's worker side (reference:
src/ray/core_worker/core_worker.cc HandlePushTask :3335 → TaskReceiver →
ordered/concurrent execution queues; python worker loop in
python/ray/_private/worker.py main_loop): the worker registers with its node
daemon, then serves ``push_task`` (stateless tasks) and
``init_actor``/``push_actor_task`` (actor hosting) over RPC. Task code runs
with this process's ClusterRuntime as the global runtime, so nested
``ray_tpu.get``/``.remote`` calls work from inside tasks.
"""

from __future__ import annotations

import asyncio
import inspect
import os
import queue
import threading
from collections import deque
from typing import Any

import cloudpickle

from ray_tpu.core.cluster.protocol import EventLoopThread, pack_reply
from ray_tpu.core.cluster.runtime import ClusterRuntime
from ray_tpu.core.exceptions import (
    ActorDiedError,
    OutOfMemoryError,
    TaskCancelledError,
    TaskError,
)
from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.core.task_spec import ActorCreationSpec, TaskSpec
from ray_tpu.utils import serialization
from ray_tpu.utils.compile_cache import ensure_compile_cache
from ray_tpu.utils.config import get_config


def _run_batch_contained(specs, run_one) -> list:
    """Run ``run_one(spec)`` for each spec in order, containing stale
    cancel_task async-interrupts that land BETWEEN tasks (see
    _SerialExecutor._run, which swallows exactly this case). An escape
    would fail the whole batch and get a healthy worker marked dead by
    the submitter."""
    replies: list = []
    while len(replies) < len(specs):
        try:
            while len(replies) < len(specs):
                replies.append(run_one(specs[len(replies)]))
        except TaskCancelledError:
            continue  # late interrupt for an already-finished task
    return replies


class _SerialExecutor:
    """One-task-at-a-time executor whose worker thread survives async-raised
    interrupts. cancel_task delivers TaskCancelledError via
    PyThreadState_SetAsyncExc; if the target task finishes before delivery,
    the exception lands between tasks — a ThreadPoolExecutor thread would die
    (and max_workers=1 never replaces it, wedging the worker), this loop
    swallows it and keeps serving. Interface subset of concurrent.futures
    used by loop.run_in_executor: submit() -> Future."""

    def __init__(self):
        import concurrent.futures
        import queue as _q

        self._futures = concurrent.futures
        self._q: "_q.Queue" = _q.Queue()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="task-exec")
        self._thread.start()

    def submit(self, fn, *args):
        fut = self._futures.Future()
        self._q.put((fut, fn, args))
        return fut

    def shutdown(self, wait=True):  # noqa: ARG002 - interface compat
        self._q.put(None)

    def _run(self):
        while True:
            try:
                item = self._q.get()
                if item is None:
                    return
                fut, fn, args = item
                if not fut.set_running_or_notify_cancel():
                    continue
                try:
                    fut.set_result(fn(*args))
                except BaseException as e:  # noqa: BLE001
                    fut.set_exception(e)
            except TaskCancelledError:
                continue  # late async interrupt landed between tasks


class WorkerProcess:
    def __init__(self):
        head = os.environ["RTPU_HEAD"].split(":")
        daemon = os.environ["RTPU_NODE_DAEMON"].split(":")
        self.runtime = ClusterRuntime(
            head[0], int(head[1]),
            node_daemon_addr=(daemon[0], int(daemon[1])),
            is_worker=True,
        )
        # Bind the process-global worker so user code sees the cluster runtime.
        from ray_tpu.core.worker import global_worker
        from ray_tpu.utils.ids import JobID

        global_worker.runtime = self.runtime
        global_worker.worker_id = self.runtime.worker_id
        global_worker.node_id = self.runtime.node_id
        global_worker.job_id = JobID.from_random()
        global_worker.mode = "cluster"

        self._io = EventLoopThread.get()
        srv = self.runtime.server
        srv.register("push_task", self._push_task)
        srv.register("init_actor", self._init_actor)
        # Fast-path frames, dispatched INLINE in the read loop (no task
        # spawn, no reply future): the execution thread deserializes the
        # spec, runs it, packs the reply itself, and posts the pre-packed
        # bytes back with one loop wake (reference: the direct-call path in
        # core_worker.cc answers PushTask from the executing thread).
        # push_actor_task (streaming) MUST ride the same inline dispatch:
        # mixing an inline route with a task-spawned one would let later
        # calls reach the mailbox before an earlier streaming call.
        srv.register_raw("push_task_batch", self._push_task_batch_raw)
        srv.register_raw("push_actor_task", self._push_actor_call_raw)
        srv.register_raw("push_actor_calls", self._push_actor_calls_raw)
        srv.register("cancel_task", self._cancel_task)
        srv.register("exit_worker", self._exit_worker)
        # On-demand profiling plane (head -> node_daemon -> here): captures
        # run on an executor thread so they sample task/actor execution
        # instead of blocking behind it. (dump_stack / memory_snapshot
        # one-shots are registered by ClusterRuntime for every process.)
        srv.register("profile", self._profile)
        # Cancellation state: ids cancelled before start, and the thread
        # currently executing each task (for async interrupt).
        self._cancelled_tasks: set[str] = set()
        self._running_tasks: dict[str, int] = {}  # task_id hex -> thread ident
        # Deserialized-function cache keyed by the exact code blob — repeat
        # submissions of the same @remote function skip the unpickle
        # (reference: function_manager.py caches imported remote functions).
        # Only specs from registry-less submitters (client-mode proxies)
        # still embed blobs; registry specs use _registry_cache below.
        self._fn_cache: dict[bytes, Any] = {}
        # Registry-fetched definitions, LRU-bounded by serialized size
        # (reference: FunctionManager fetch-and-cache from the GCS table).
        from ray_tpu.core.fn_registry import FnCache

        self._registry_cache = FnCache(get_config().fn_cache_max_bytes)
        self._task_executor = _SerialExecutor()
        # Cross-thread reply buffer: execution threads enqueue pre-packed
        # reply frames the moment each call finishes (nothing is ever held
        # across a later execution), and ONE loop wake drains everything
        # enqueued since the last drain — the same coalescing the submit
        # buffer uses on the driver side. Under load one self-pipe write
        # covers a burst of replies; when idle, the wake is immediate.
        self._reply_buf: deque = deque()
        self._reply_wake = False
        self._reply_lock = threading.Lock()
        self._actor_instance: Any = None
        self._actor_id_hex: str | None = None
        self._actor_mailbox: "queue.Queue" = queue.Queue()
        self._actor_loop: asyncio.AbstractEventLoop | None = None
        self._actor_pool = None
        self._exit_event = threading.Event()

        self.node_id_hex = os.environ.get("RTPU_NODE_ID", "")
        self.runtime._daemon.call(
            "register_worker_proc",
            worker_id=self.runtime.worker_id.hex(),
            host=self.runtime.addr[0], port=self.runtime.addr[1],
            pid=os.getpid(),
            # Containerized workers see a different pid than the daemon's
            # Popen (the runner's); the fork nonce is the reliable join key.
            nonce=os.environ.get("RTPU_WORKER_NONCE", ""),
        )
        # Task events, spans, and metric snapshots all reach the head via
        # the runtime's telemetry flusher (ClusterRuntime._telemetry_flusher
        # — reference: TaskEventBuffer flushing into GcsTaskManager plus the
        # metrics agent push); workers need no extra thread here.

    # ------------------------------------------------------------------ tasks
    async def _push_task(self, conn, spec_blob: bytes):
        spec: TaskSpec = serialization.loads_spec(spec_blob)
        loop = asyncio.get_running_loop()
        emit = self._stream_emitter(conn, loop, spec) \
            if spec.num_returns == "streaming" else None
        # Serial execution: one normal task at a time per leased worker
        # (reference semantics — a worker runs one task; pipelined pushes
        # queue here, matching lease-based resource accounting).
        return await loop.run_in_executor(self._task_executor,
                                          self._execute_task, spec, emit)

    def _push_task_batch_raw(self, conn, msg: dict):
        """Batched push, raw-dispatched: N specs in one frame, executed in
        order, N results in one reply. Spec deserialization AND reply
        packing happen on the execution thread; the io loop's only work per
        batch is one enqueue and one write (the per-task dispatch
        task/future/executor hop dominated small-task throughput on
        few-core hosts)."""
        self._task_executor.submit(
            self._run_task_batch, msg["a"]["blobs"], msg.get("i"), conn,
            asyncio.get_running_loop())

    def _post_reply(self, loop, conn, frame: bytes) -> None:
        """Ship one pre-packed reply from an execution thread: enqueued
        immediately (never held behind a later execution), with coalesced
        loop wakes — one self-pipe write covers every reply buffered until
        the drain runs."""
        with self._reply_lock:
            self._reply_buf.append((conn, frame))
            wake = not self._reply_wake
            self._reply_wake = True
        if wake:
            loop.call_soon_threadsafe(self._drain_replies)

    def _drain_replies(self) -> None:
        with self._reply_lock:
            items = list(self._reply_buf)
            self._reply_buf.clear()
            self._reply_wake = False
        for conn, frame in items:
            conn.post(frame)

    def _run_task_batch(self, blobs: list, rid, conn, loop) -> None:
        try:
            specs = [serialization.loads_spec(b) for b in blobs]
            replies = self._execute_batch(specs)
            data = pack_reply(rid, {"replies": replies})
        except BaseException as e:  # noqa: BLE001 - client must not hang
            data = pack_reply(rid, err=f"{type(e).__name__}: {e}")
        self._post_reply(loop, conn, data)

    def _execute_batch(self, specs) -> list:
        return _run_batch_contained(
            specs, lambda spec: self._execute_task(spec, None))

    def _stream_emitter(self, conn, loop, spec):
        """Item pump for streaming tasks: each yield goes back to the owner
        as a notify frame on the submitting connection (TCP ordering puts
        every item before the final reply — reference: streamed generator
        returns report each dynamic return to the owner as produced)."""
        cfg = get_config()

        def emit(index: int, value) -> None:
            from ray_tpu.utils.ids import ObjectID

            blob = serialization.serialize(value)
            tid = spec.task_id.hex()
            if len(blob) <= cfg.inline_object_max_bytes:
                coro = conn.notify("stream_item", task_id=tid, index=index,
                                   data=blob)
            else:
                oid = ObjectID.for_task_return(spec.task_id, index)
                self.runtime._store_blob(
                    oid, blob, spec.owner_id or self.runtime.worker_id)
                coro = conn.notify("stream_item", task_id=tid, index=index,
                                   location=self.runtime.worker_id.hex(),
                                   size=len(blob))
            asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=60)

        return emit

    def _run_stream(self, spec, result, emit) -> dict:
        """Drive a streaming task's generator; returns the end-of-stream
        reply ({"stream_count": N} or the error for the end marker).
        Registered in _running_tasks for the whole drive so cancel_task can
        interrupt mid-stream (the generator body runs HERE, not in the
        user-function call that produced the generator object)."""
        tid_hex = spec.task_id.hex()
        self._running_tasks[tid_hex] = threading.get_ident()
        i = 0
        try:
            for v in result:
                if tid_hex in self._cancelled_tasks:
                    raise TaskCancelledError()
                emit(i, v)
                i += 1
        except BaseException as e:  # noqa: BLE001
            err = e if isinstance(e, (TaskError, ActorDiedError,
                                      TaskCancelledError,
                                      OutOfMemoryError)) \
                else TaskError(e, task_desc=spec.name)
            return {"results": [{"data": serialization.serialize(err)}],
                    "stream_error": True}
        finally:
            self._running_tasks.pop(tid_hex, None)
            self._cancelled_tasks.discard(tid_hex)
        return {"stream_count": i}

    async def _cancel_task(self, conn, task_id: str, force: bool = False):
        """Best-effort cancel (reference: CoreWorker::HandleCancelTask —
        interrupt the running task or drop it from the queue). A running
        task is interrupted by raising TaskCancelledError asynchronously in
        its executing thread."""
        self._cancelled_tasks.add(task_id)
        tident = self._running_tasks.get(task_id)
        if tident is not None:
            import ctypes

            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(tident), ctypes.py_object(TaskCancelledError))
        return {"ok": True, "was_running": tident is not None}

    def _execute_task(self, spec: TaskSpec, stream_emit=None) -> dict:
        from ray_tpu.core.events import task_execution
        from ray_tpu.core.worker import set_task_context

        return_ids = spec.return_ids()
        tid_hex = spec.task_id.hex()
        if tid_hex in self._cancelled_tasks:
            self._cancelled_tasks.discard(tid_hex)
            blob = serialization.serialize(TaskCancelledError())
            return {"results": [{"data": blob} for _ in return_ids]}
        self._running_tasks[tid_hex] = threading.get_ident()
        try:
            if spec.runtime_env:
                from ray_tpu.runtime_env import get_manager

                get_manager().ensure(spec.runtime_env, self.runtime)
            fn = self._load_definition(spec.fn_id, spec.fn_blob)
            args, kwargs = serialization.deserialize(spec.args_blob)
            args = self._resolve(args)
            kwargs = self._resolve(kwargs)
            set_task_context(spec.task_id, spec.actor_id, spec.resources)
            try:
                with task_execution(spec, self.runtime.worker_id.hex(),
                                    node_id=self.node_id_hex):
                    result = fn(*args, **kwargs)
            finally:
                set_task_context(None, None, None)
        except BaseException as e:  # noqa: BLE001
            err = e if isinstance(e, (TaskError, ActorDiedError,
                                      TaskCancelledError,
                                      OutOfMemoryError)) \
                else TaskError(e, task_desc=spec.name)
            if not isinstance(e, TaskCancelledError):
                # Application exceptions are terminal in cluster mode: the
                # submitter's retry budget only covers SYSTEM failures
                # (worker death — RpcError/OSError on the push), so this
                # path never fires for an attempt that will be retried.
                from ray_tpu.core import flight_recorder

                flight_recorder.record(
                    "task_failure", reason=repr(e), task_id=tid_hex,
                    node_id=self.node_id_hex,
                    extra={"task": spec.name,
                           "worker_id": self.runtime.worker_id.hex()})
            blob = serialization.serialize(err)
            return {"results": [{"data": blob} for _ in return_ids]}
        finally:
            self._running_tasks.pop(tid_hex, None)
            self._cancelled_tasks.discard(tid_hex)
        if stream_emit is not None:
            return self._run_stream(spec, result, stream_emit)
        return {"results": self._package_results(spec, return_ids, result)}

    def _load_definition(self, fn_id: str, fn_blob: bytes):
        """Resolve a task's callable: registry cache hit, registry fetch on
        miss (exactly once per definition per worker), or the embedded-blob
        legacy path for registry-less submitters."""
        if fn_id:
            from ray_tpu.core.cluster.runtime import observe_ctrl_fn

            fn = self._registry_cache.get(fn_id)
            if fn is not None:
                observe_ctrl_fn("hit", 0)
                return fn
            blob = fn_blob or self.runtime.fetch_function(fn_id)
            fn = serialization.loads_function(blob)
            self._registry_cache.put(fn_id, fn, len(blob))
            return fn
        fn = self._fn_cache.get(fn_blob)
        if fn is None:
            fn = serialization.loads_function(fn_blob)
            if len(self._fn_cache) > 256:
                self._fn_cache.clear()
            self._fn_cache[fn_blob] = fn
        return fn

    def _resolve(self, obj):
        if isinstance(obj, ObjectRef):
            return self.runtime.get([obj])[0]
        if isinstance(obj, tuple):
            return tuple(self._resolve(o) if isinstance(o, ObjectRef) else o for o in obj)
        if isinstance(obj, list):
            return obj
        if isinstance(obj, dict):
            return {k: (self._resolve(v) if isinstance(v, ObjectRef) else v)
                    for k, v in obj.items()}
        return obj

    def _package_results(self, spec: TaskSpec, return_ids, result) -> list[dict]:
        cfg = get_config()
        values = [result] if spec.num_returns == 1 else list(result)
        if len(values) != spec.num_returns:
            err = TaskError(
                ValueError(f"declared num_returns={spec.num_returns}, got {len(values)}"),
                task_desc=spec.name)
            blob = serialization.serialize(err)
            return [{"data": blob} for _ in return_ids]
        out = []
        for oid, v in zip(return_ids, values):
            if isinstance(v, ObjectRef):
                v = self.runtime.get([v])[0]
            blob = serialization.serialize(v)
            if len(blob) <= cfg.inline_object_max_bytes:
                out.append({"data": blob})
            else:
                # Large result: goes to the node shm arena when available
                # (same-node readers get it zero-copy without an RPC), else
                # stays in our process store; either way the owner records
                # our location for cross-node fetches (reference: results
                # over max_direct_call_object_size go to plasma at the
                # executor).
                self.runtime._store_blob(
                    oid, blob, spec.owner_id or self.runtime.worker_id)
                out.append({"location": self.runtime.worker_id.hex(),
                            "size": len(blob)})
        return out

    # ------------------------------------------------------------------ actors
    async def _init_actor(self, conn, actor_id: str, spec_blob: bytes):
        spec: ActorCreationSpec = cloudpickle.loads(spec_blob)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self._do_init_actor, actor_id, spec)

    def _do_init_actor(self, actor_id: str, spec: ActorCreationSpec) -> dict:
        try:
            if spec.runtime_env:
                from ray_tpu.runtime_env import get_manager

                get_manager().ensure(spec.runtime_env, self.runtime)
            cls = self._load_definition(getattr(spec, "cls_id", ""),
                                        spec.cls_blob)
            args, kwargs = serialization.deserialize(spec.args_blob)
            self._actor_instance = cls(*self._resolve(args), **self._resolve(kwargs))
            self._actor_id_hex = actor_id
            if any(
                inspect.iscoroutinefunction(getattr(type(self._actor_instance), m, None))
                for m in dir(type(self._actor_instance)) if not m.startswith("__")
            ):
                self._actor_loop = asyncio.new_event_loop()
                threading.Thread(target=self._actor_loop.run_forever, daemon=True).start()
            if spec.max_concurrency > 1:
                from concurrent.futures import ThreadPoolExecutor

                self._actor_pool = ThreadPoolExecutor(max_workers=spec.max_concurrency)
            # Ordered mailbox consumer (reference: ordered actor execution queue).
            threading.Thread(target=self._actor_consumer, daemon=True).start()
            return {"ok": True}
        except BaseException as e:  # noqa: BLE001
            return {"ok": False, "error": f"__init__ failed: {e!r}"}

    def _actor_consumer(self):
        while True:
            item = self._actor_mailbox.get()
            if item is None:
                return
            if item[0] == "__call__":
                # Fast-path call (raw-dispatched push_actor_call(s) frame):
                # decode the spec HERE (off the io loop), execute in
                # mailbox order, serialize the reply on this thread, and
                # post pre-packed bytes — the loop's only per-call work is
                # one write, and each reply ships the moment its call
                # finishes (a later slow method never holds an earlier
                # result hostage; the coalescing writer still merges
                # replies landing in the same loop tick into one syscall).
                # Concurrent execution modes (async methods, concurrency
                # pools, injected fns) run on their own threads and post
                # their replies the same way when THEY finish, so replies
                # correlate out-of-order by request id.
                _, spec_blob, rid, conn, loop = item
                try:
                    spec: TaskSpec = serialization.loads_spec(spec_blob)
                except BaseException as e:  # noqa: BLE001
                    loop.call_soon_threadsafe(conn.post, pack_reply(
                        rid, err=f"{type(e).__name__}: {e}"))
                    continue
                if not self._dispatch_concurrent(spec, rid, conn, loop):
                    self._run_actor_call(spec, rid, conn, loop)
                continue

    def _dispatch_concurrent(self, spec: TaskSpec, rid, conn, loop) -> bool:
        """Route a fast-path call that must NOT run on the ordered consumer
        thread (async methods, concurrency pools, injected long-running
        fns) to its executor. Returns False for plain sync methods — the
        consumer runs those inline, preserving mailbox order."""
        if spec.method_name == "__rtpu_call_fn__":
            threading.Thread(target=self._run_actor_call,
                             args=(spec, rid, conn, loop),
                             daemon=True).start()
            return True
        method = getattr(type(self._actor_instance), spec.method_name, None)
        if inspect.iscoroutinefunction(method) or self._actor_pool is not None:
            if self._actor_pool is not None:
                self._actor_pool.submit(self._run_actor_call,
                                        spec, rid, conn, loop)
            else:
                threading.Thread(target=self._run_actor_call,
                                 args=(spec, rid, conn, loop),
                                 daemon=True).start()
            return True
        return False

    def _run_actor_call(self, spec: TaskSpec, rid, conn, loop) -> None:
        """Execute one fast-path call and post its reply: serialization on
        the execution thread, coalesced loop wakes (_post_reply), and the
        coalescing writer merges frames shipped in one tick into one
        syscall."""
        reply = self._exec_actor_reply(spec, loop, conn)
        try:
            data = pack_reply(rid, reply)
        except BaseException as e:  # noqa: BLE001 - unpackable reply value
            data = pack_reply(rid, err=f"{type(e).__name__}: {e}")
        self._post_reply(loop, conn, data)

    def _exec_actor_reply(self, spec: TaskSpec, loop, conn=None) -> dict:
        from ray_tpu.core.events import task_execution
        from ray_tpu.core.worker import set_task_context

        return_ids = spec.return_ids()
        try:
            args, kwargs = serialization.deserialize(spec.args_blob)
            args, kwargs = self._resolve(args), self._resolve(kwargs)
            if spec.method_name == "__rtpu_call_fn__":
                # Internal hook: fn(instance, *args) in actor context
                # (reference: __ray_call__; compiled-graph loop installer).
                import functools

                method = functools.partial(args[0], self._actor_instance)
                args = args[1:]
            else:
                method = getattr(self._actor_instance, spec.method_name)
            set_task_context(spec.task_id, spec.actor_id, spec.resources)
            try:
                with task_execution(spec, self.runtime.worker_id.hex(),
                                    node_id=self.node_id_hex):
                    if inspect.iscoroutinefunction(method):
                        fut = asyncio.run_coroutine_threadsafe(
                            method(*args, **kwargs), self._actor_loop)
                        result = fut.result()
                    else:
                        result = method(*args, **kwargs)
            finally:
                set_task_context(None, None, None)
            if spec.num_returns == "streaming" and conn is not None:
                emit = self._stream_emitter(conn, loop, spec)
                reply = self._run_stream(spec, result, emit)
            else:
                reply = {"results": self._package_results(spec, return_ids,
                                                          result)}
        except BaseException as e:  # noqa: BLE001
            err = e if isinstance(e, (TaskError, ActorDiedError,
                                      TaskCancelledError,
                                      OutOfMemoryError)) \
                else TaskError(e, task_desc=spec.method_name or "")
            reply = {"results": [{"data": serialization.serialize(err)}
                                 for _ in return_ids]}
        return reply

    def _push_actor_call_raw(self, conn, msg: dict):
        """Direct actor call (raw-dispatched): the read loop's entire work
        is one mailbox enqueue. Replies correlate by request id, so calls
        finishing out of order (async actors, pools) answer out of order —
        a sync 1:1 call is one RPC round trip with no reply future, no
        dispatch task, and no loop hop between execution and reply
        serialization. Streaming calls (legacy push_actor_task frames)
        take the same route: _exec_actor_reply drives the generator and
        the stream-end reply posts like any other."""
        rid = msg.get("i")
        if self._actor_instance is None:
            conn.post(pack_reply(rid, {
                "dead": True, "reason": "no actor hosted in this worker"}))
            return
        self._actor_mailbox.put((
            "__call__", msg["a"]["spec_blob"], rid, conn,
            asyncio.get_running_loop()))

    def _push_actor_calls_raw(self, conn, msg: dict):
        """Multi-call frame: N individually-correlated calls ride one frame
        (one decode, N mailbox items); replies flow back per call, batched
        per consumer sweep (see _actor_consumer's reply flushing)."""
        calls = msg.get("c") or []
        if self._actor_instance is None:
            conn.post([pack_reply(rid, {
                "dead": True, "reason": "no actor hosted in this worker"})
                for rid, _ in calls])
            return
        loop = asyncio.get_running_loop()
        put = self._actor_mailbox.put
        for rid, blob in calls:
            put(("__call__", blob, rid, conn, loop))

    # ------------------------------------------------------------- profiling
    async def _profile(self, conn, seconds: float = 1.0,
                       sample_hz: float = 0.0):
        """One capture of THIS worker: stack samples + (guarded) XLA trace +
        memory snapshot. Runs on the default executor — the serial task
        executor keeps executing, which is the whole point of sampling it."""
        import functools

        from ray_tpu.profiling import capture_profile

        loop = asyncio.get_running_loop()
        meta = {"kind": "worker", "worker_id": self.runtime.worker_id.hex(),
                "node_id": self.node_id_hex,
                "actor_id": self._actor_id_hex or ""}
        return await loop.run_in_executor(None, functools.partial(
            capture_profile, seconds, sample_hz=sample_hz or None,
            meta=meta))

    async def _exit_worker(self, conn):
        self._exit_event.set()
        return {"ok": True}

    def serve_forever(self):
        self._exit_event.wait()


def _parent_watchdog():
    """Exit if the spawning daemon process dies (orphan prevention —
    reference: workers die with their raylet via the IPC socket)."""
    parent = int(os.environ.get("RTPU_PARENT_PID", "0"))
    if not parent:
        return
    import time as _t

    def watch():
        while True:
            try:
                os.kill(parent, 0)
            except OSError:
                os._exit(0)
            _t.sleep(1.0)

    threading.Thread(target=watch, daemon=True).start()


def _install_sigusr2_dump():
    """Hung-worker last resort: SIGUSR2 dumps every thread's stack into a
    flight-recorder bundle (kind ``worker_stacks``) that survives the
    process. The node daemon sends it before escalating to SIGKILL on
    unresponsive workers, so a post-mortem always has the final stacks even
    when the RPC plane is wedged."""
    import signal

    def _dump(signum, frame):  # noqa: ARG001 - signal handler signature
        try:
            from ray_tpu.core import flight_recorder
            from ray_tpu.profiling.sampler import dump_stacks

            # local_only: the dump must not block on a head RPC — the RPC
            # plane being wedged (or the kill-grace window expiring) is
            # exactly when this handler fires.
            flight_recorder.record(
                "worker_stacks", reason="SIGUSR2 stack dump",
                node_id=os.environ.get("RTPU_NODE_ID", ""),
                extra={"stacks": dump_stacks(), "pid": os.getpid()},
                local_only=True)
        except Exception:
            pass  # a dump must never make a dying worker die harder

    signal.signal(signal.SIGUSR2, _dump)


def main():
    # SIGUSR1 dumps all thread stacks to the worker log — the first tool to
    # reach for when a worker wedges (reference: ray stack / py-spy dump).
    import faulthandler
    import signal

    faulthandler.register(signal.SIGUSR1, all_threads=True)
    _install_sigusr2_dump()
    # Tasks that compile share one persistent cache with the driver and
    # with each other (sets an environment default; imports no JAX).
    ensure_compile_cache()
    _parent_watchdog()
    wp = WorkerProcess()
    wp.serve_forever()


if __name__ == "__main__":
    main()
