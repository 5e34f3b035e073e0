"""Elastic training manager.

Parity: `python/paddle/distributed/fleet/elastic/manager.py:127`
(`ElasticManager`: etcd registration :229, watch/scale callbacks :244,
fault-tolerant restart via the launcher).

TPU-native scope: within a slice, chip failure kills the whole SPMD
program — elasticity happens at the JOB level: a watchdog restarts the
training process and the program resumes from the latest (orbax) sharded
checkpoint. This manager implements that restart loop with a file-based
heartbeat/KV (no etcd in-image); the etcd transport can be slotted in via
the same Store interface.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time


class FileStore:
    """KV + heartbeat store on a shared filesystem (etcd stand-in)."""

    def __init__(self, root):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def put(self, key, value):
        # atomic write: a concurrent alive_nodes() reader must never see a
        # truncated file; the dot prefix keeps in-flight temps out of the
        # heartbeat_* directory listing
        path = os.path.join(self.root, key)
        tmp = os.path.join(self.root, f".{key}.tmp{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(value, f)
        os.replace(tmp, path)

    def get(self, key, default=None):
        p = os.path.join(self.root, key)
        if not os.path.exists(p):
            return default
        try:
            with open(p) as f:
                return json.load(f)
        except (json.JSONDecodeError, OSError):
            return default

    def heartbeat(self, node_id):
        self.put(f"heartbeat_{node_id}", {"ts": time.time()})

    def alive_nodes(self, timeout=30.0):
        now = time.time()
        out = []
        for f in os.listdir(self.root):
            if f.startswith("heartbeat_") and ".tmp" not in f:
                hb = self.get(f)
                if hb and now - hb["ts"] < timeout:
                    out.append(f[len("heartbeat_"):])
        return sorted(out)


class KVMasterServer:
    """TCP KV master (the launcher master.py HTTP/etcd-server role): a
    json-line protocol over one listening socket. Second Store transport
    proving the FileStore seam is real."""

    def __init__(self, host="127.0.0.1", port=0):
        import socketserver
        import threading

        kv = {}
        lock = threading.Lock()

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                for line in self.rfile:
                    try:
                        req = json.loads(line)
                    except json.JSONDecodeError:
                        break
                    with lock:
                        if req["op"] == "put":
                            kv[req["key"]] = req["value"]
                            resp = {"ok": True}
                        elif req["op"] == "get":
                            resp = {"ok": True,
                                    "value": kv.get(req["key"])}
                        elif req["op"] == "list":
                            pfx = req.get("prefix", "")
                            resp = {"ok": True,
                                    "items": {k: v for k, v in kv.items()
                                              if k.startswith(pfx)}}
                        else:
                            resp = {"ok": False}
                    self.wfile.write((json.dumps(resp) + "\n").encode())
                    self.wfile.flush()

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._server.shutdown()


class TcpStore:
    """Store client with the same interface as FileStore, over a
    KVMasterServer (PADDLE_ELASTIC_STORE=tcp://host:port)."""

    def __init__(self, host, port):
        import socket
        self._sock = socket.create_connection((host, int(port)),
                                              timeout=30)
        self._rfile = self._sock.makefile("r")

    def _call(self, req):
        self._sock.sendall((json.dumps(req) + "\n").encode())
        return json.loads(self._rfile.readline())

    def put(self, key, value):
        self._call({"op": "put", "key": key, "value": value})

    def get(self, key, default=None):
        resp = self._call({"op": "get", "key": key})
        v = resp.get("value")
        return default if v is None else v

    def heartbeat(self, node_id):
        self.put(f"heartbeat_{node_id}", {"ts": time.time()})

    def alive_nodes(self, timeout=30.0):
        now = time.time()
        items = self._call({"op": "list",
                            "prefix": "heartbeat_"}).get("items", {})
        return sorted(k[len("heartbeat_"):] for k, v in items.items()
                      if v and now - v["ts"] < timeout)


def make_store(spec):
    """'tcp://host:port' -> TcpStore; anything else -> FileStore root."""
    if spec.startswith("tcp://"):
        host, port = spec[len("tcp://"):].rsplit(":", 1)
        return TcpStore(host, port)
    return FileStore(spec)


class ElasticManager:
    """manager.py:127 parity: node registration (:229), membership
    watch + scale in/out with RANK REGENERATION (:244), fault-tolerant
    restart. On a membership change the leader (lowest alive node id)
    publishes a new `generation` {gen, nodes}; every node kills its
    training process and relaunches it with regenerated ranks
    (NODE_RANK = index in the sorted alive set, PADDLE_NNODES = world);
    nodes scaled out of the membership exit cleanly."""

    def __init__(self, args=None, store_root=None, max_restarts=3,
                 heartbeat_interval=5.0, min_nodes=1, max_nodes=None,
                 settle_checks=2):
        self.store = make_store(store_root or
                                os.environ.get("PADDLE_ELASTIC_STORE",
                                               "/tmp/paddle_tpu_elastic"))
        self.max_restarts = max_restarts
        self.heartbeat_interval = heartbeat_interval
        self.node_id = os.environ.get("PADDLE_NODE_RANK", "0")
        self.min_nodes = int(os.environ.get("PADDLE_ELASTIC_MIN_NODES",
                                            min_nodes))
        self.max_nodes = max_nodes
        self.settle_checks = settle_checks
        self.restarts = 0

    def register(self):
        """manager.py:229 parity: announce this node."""
        self.store.heartbeat(self.node_id)
        self.store.put(f"node_{self.node_id}",
                       {"pid": os.getpid(), "restarts": self.restarts})

    def watch(self):
        return self.store.alive_nodes(timeout=self.heartbeat_interval * 4)

    # ---- scale in/out ----------------------------------------------
    def _generation(self):
        return self.store.get("generation") or {"gen": 0, "nodes": []}

    def _maybe_bump_generation(self, pending):
        """Leader duty (lowest alive id): after the membership has
        differed from the current generation for `settle_checks`
        consecutive watches (debounce), publish gen+1 with the new
        node list. Returns the updated pending counter."""
        alive = self.watch()
        if not alive or alive[0] != self.node_id:
            return 0
        gen = self._generation()
        if self.max_nodes:
            alive = alive[:self.max_nodes]
        if alive == gen["nodes"] or len(alive) < self.min_nodes:
            return 0
        pending += 1
        if pending >= self.settle_checks:
            self.store.put("generation",
                           {"gen": gen["gen"] + 1, "nodes": alive})
            sys.stderr.write(
                f"[elastic] scale event: gen {gen['gen'] + 1} "
                f"nodes {alive}\n")
            return 0
        return pending

    def _spawn(self, cmd, gen):
        """Relaunch training with REGENERATED ranks for this
        generation (manager.py scale in/out -> launcher restart)."""
        env = dict(os.environ)
        nodes = gen["nodes"]
        env["PADDLE_NNODES"] = str(len(nodes))
        env["PADDLE_TRAINERS_NUM"] = str(len(nodes))
        env["NODE_RANK"] = str(nodes.index(self.node_id))
        env["PADDLE_NODE_RANK"] = env["NODE_RANK"]
        env["PADDLE_ELASTIC_GEN"] = str(gen["gen"])
        return subprocess.Popen(cmd, env=env)

    def _stop_proc(self, proc, grace=30.0):
        """Terminate the training process, heartbeating WHILE waiting
        (a graceful shutdown longer than the aliveness window must not
        make this node look dead); SIGKILL past the grace period."""
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        deadline = time.time() + grace
        while proc.poll() is None and time.time() < deadline:
            self.store.heartbeat(self.node_id)
            time.sleep(min(self.heartbeat_interval, 0.5))
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    def _handle_exit(self, returncode):
        """-> "done" | "give-up" | "restart" (shared restart
        bookkeeping for the watchdog and elastic loops)."""
        if returncode == 0:
            return "done"
        self.restarts += 1
        if self.restarts > self.max_restarts:
            return "give-up"
        sys.stderr.write(
            f"[elastic] training exited {returncode}; restart "
            f"{self.restarts}/{self.max_restarts}\n")
        return "restart"

    def run(self, cmd, elastic=False, poll_timeout=None):
        """Supervise `cmd` (the training script).

        elastic=False: plain fault-tolerant restart (watchdog).
        elastic=True: additionally watch membership; on a scale event
        every surviving node restarts `cmd` with regenerated ranks, and
        a node dropped from the membership returns "scaled-in".
        `poll_timeout` bounds either loop (tests)."""
        deadline = time.time() + poll_timeout if poll_timeout else None
        from ..core.place import holds_accelerator
        if holds_accelerator():
            raise RuntimeError(
                "the elastic supervisor has initialised a jax "
                "accelerator backend and so holds the chip: the "
                "training process it starts would fail or hang. "
                "Supervise from a process that imports paddle_tpu but "
                "runs no jax computation.")
        if not elastic:
            while True:
                self.register()
                proc = subprocess.Popen(cmd)
                while proc.poll() is None:
                    if deadline and time.time() > deadline:
                        self._stop_proc(proc)
                        return "timeout"
                    self.store.heartbeat(self.node_id)
                    time.sleep(self.heartbeat_interval)
                verdict = self._handle_exit(proc.returncode)
                if verdict == "done":
                    return 0
                if verdict == "give-up":
                    return proc.returncode

        self.register()
        my_gen = -1
        proc = None
        pending = 0
        try:
            while True:
                if deadline and time.time() > deadline:
                    self._stop_proc(proc)
                    return "timeout"
                self.store.heartbeat(self.node_id)
                pending = self._maybe_bump_generation(pending)
                gen = self._generation()
                if gen["gen"] != my_gen and gen["nodes"]:
                    if self.node_id not in gen["nodes"]:
                        if my_gen == -1:
                            if self.max_nodes and \
                                    len(gen["nodes"]) >= self.max_nodes:
                                alive = set(self.watch())
                                if all(n in alive
                                       for n in gen["nodes"]):
                                    # cluster full of LIVE nodes: no
                                    # slot is coming — don't spin
                                    # forever. (A dead member means a
                                    # reshuffle is imminent; keep
                                    # waiting to replace it.)
                                    return "not-admitted"
                            # joining node: keep heartbeating until the
                            # leader includes us in a future generation
                            time.sleep(self.heartbeat_interval)
                            continue
                        self._stop_proc(proc)
                        return "scaled-in"
                    self._stop_proc(proc)
                    my_gen = gen["gen"]
                    self.restarts = 0
                    proc = self._spawn(cmd, gen)
                elif proc is not None and proc.poll() is not None:
                    verdict = self._handle_exit(proc.returncode)
                    if verdict == "done":
                        return 0
                    if verdict == "give-up":
                        return proc.returncode
                    # respawn from the ALREADY-VALIDATED generation (a
                    # fresh read could exclude this node mid-loop)
                    proc = self._spawn(cmd, gen)
                time.sleep(self.heartbeat_interval)
        finally:
            if proc is not None and proc.poll() is None:
                proc.terminate()
