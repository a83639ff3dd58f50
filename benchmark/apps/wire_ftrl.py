"""The ``wire_ftrl`` app: one ``ShardServer`` holding the whole key range
on the chip, in the process that owns the chip; workers are CPU-pinned
child processes (``apps/wire_client.py``) over real TCP.

What the benchmark takes from the program: ``ShardServer`` (its
``counters``, its published ``state``), ``ServerHandle`` in the clients,
``hostenv.force_cpu``. The server's own ``apply`` timing is not read: it
times the enqueue of an asynchronous jit (ROADMAP S5); the trace is.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "wire_client.py")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control(ctx, precision: str = "bfloat16") -> dict:
    """The controls of the wire cell at its own size, no chip needed: the
    single-client prefix answered by the reference in ``precision`` (against
    the float32 reference), and the guarantee broken - every push applied
    twice - as the witness rows would then read."""
    from benchmark.apps import wire_client as wc
    from benchmark.harness.checks import worst_gap
    from benchmark.harness.ref_ftrl import RefFtrl

    st, spec = ctx.config["settings"], ctx.traffic
    hyper = {k: st[k] for k in ("alpha", "beta", "lambda_l1", "lambda_l2")}
    ks = wc.KeySpace(int(st["num_keys"]), int(spec["clients"]), spec, ctx.config["data"])
    rng = np.random.default_rng([int(ctx.seed), 0xC11E, 0])
    pushes = list(wc.prefix_pushes(rng, ks, spec))
    universe = np.concatenate([k for k, _ in pushes])
    ref, low = RefFtrl(universe, hyper), RefFtrl(universe, hyper, precision)
    n_pull, gaps = int(spec["pull_keys"]), []
    for keys, grad in pushes:
        idx = ref.index(keys)
        ref.push(idx, grad)
        low.push(idx, grad)
        gaps.append(worst_gap(low.weights(idx[:n_pull]), ref.weights(idx[:n_pull])))
    g = (3.0 * rng.normal(size=4096)).astype(np.float32)
    twice = RefFtrl(np.arange(len(g)), hyper)
    twice.push(np.arange(len(g)), g)
    twice.push(np.arange(len(g)), g)
    return {
        "prefix.pull_gap": max(gaps),
        "witness.z_gap": worst_gap(twice.z, g),
        "witness.n_gap": worst_gap(twice.n, g * g),
    }


class Session:
    def __init__(self, ctx):
        from parameter_server_tpu.kv.updaters import Ftrl
        from parameter_server_tpu.parallel.multislice import ShardServer
        from parameter_server_tpu.utils.keyrange import KeyRange

        self.ctx = ctx
        st = ctx.config["settings"]
        self.num_keys = int(st["num_keys"])
        self.hyper = {k: st[k] for k in ("alpha", "beta", "lambda_l1", "lambda_l2")}
        self.clients = int(ctx.traffic["clients"])
        self.srv = ShardServer(Ftrl(**self.hyper), KeyRange(0, self.num_keys)).start()
        self.procs: list = []
        self.outs = [os.path.join(ctx.workdir, f"client{c}.npz") for c in range(self.clients)]

    def spawn(self) -> None:
        from parameter_server_tpu.utils.hostenv import force_cpu

        env = force_cpu(dict(os.environ))
        env["PYTHONPATH"] = os.pathsep.join([ROOT, env.get("PYTHONPATH", "")])
        for c in range(self.clients):
            job = {
                "client": c, "clients": self.clients, "seed": self.ctx.seed,
                "address": self.srv.address, "num_keys": self.num_keys,
                "traffic": self.ctx.traffic, "data": self.ctx.config["data"],
                "hyper": self.hyper, "out": self.outs[c],
            }
            if os.path.exists(self.outs[c]):
                os.remove(self.outs[c])
            self.procs.append(subprocess.Popen(
                [sys.executable, CLIENT, json.dumps(job)], env=env,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, bufsize=1,
            ))
            if self.ctx.traffic.get("pin_cores"):
                # one core a client, the rest for the server: no migration,
                # and the clients never take a core the server is using
                os.sched_setaffinity(self.procs[-1].pid, {c})
        if self.ctx.traffic.get("pin_cores"):
            os.sched_setaffinity(0, set(range(self.clients, os.cpu_count())))

    def expect(self, c: int, word: str) -> list:
        line = self.procs[c].stdout.readline().split()
        if not line or line[0] != word:
            raise RuntimeError(f"client {c} said {line!r}, not {word} (exit {self.procs[c].poll()})")
        return line

    def tell(self, word: str) -> None:
        for p in self.procs:
            p.stdin.write(word + "\n")
            p.stdin.flush()

    def warm_shapes(self) -> None:
        """Compile the apply's pow-2 union shapes that coalescing can reach
        (up to clients x push_keys rows) with zero-gradient pushes: an FTRL
        step at g = 0 changes nothing."""
        from parameter_server_tpu.parallel.multislice import ServerHandle
        from parameter_server_tpu.utils.config import PSConfig

        n = int(self.ctx.traffic["push_keys"])
        top = 1 << (self.clients * n - 1).bit_length()
        h = ServerHandle(self.srv.address, 0, 10_000, PSConfig(), range_size=self.num_keys)
        try:
            size = 2 * n
            while size <= top:
                h.push(np.arange(1, size + 1, dtype=np.int64), np.zeros(size, np.float32))
                size *= 2
        finally:
            h.close()

    def collect(self, timeout: float = 120.0) -> list:
        logs = []
        for c, p in enumerate(self.procs):
            rc = p.wait(timeout=timeout)
            if rc != 0:
                raise RuntimeError(f"client {c} exited {rc}")
            logs.append(dict(np.load(self.outs[c])))
        return logs

    def rows(self, keys: np.ndarray) -> dict:
        """The server's published ``z`` and ``n`` at ``keys``, read on the
        device in blocks of one fixed shape."""
        from benchmark.harness.readback import read_rows

        return read_rows(self.srv.state, keys, 1 << 16)

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                if f is not None:
                    f.close()
        self.srv.server.stop()
