#!/usr/bin/env python3
"""One closed-loop worker of the wire tier, a CPU-pinned process speaking
real TCP through the program's ``ServerHandle``. Started by
``apps/wire_ftrl.py``; never touches the chip.

Protocol with the parent, over stdin/stdout lines: client 0 first runs the
single-client prefix and prints ``PREFIX``; every client then waits for
``GO``, runs ``warm_trips`` round trips, prints ``READY <mean trip s>`` and
keeps going until it reads ``STOP``, finishes the round trip it is in,
writes its log and exits.

A round trip is one push of ``push_keys`` keys and one pull of ``pull_keys``
keys, no think time beyond drawing the next key sets. Keys are Zipf over
the deployment's categorical vocabulary, hashed into the table's rows; the
last ``witness_keys`` of every push are rows no other push ever names, so
after the run the server's state there is exactly one FTRL step of that
push's gradient whatever was coalesced with what, and the pull that
follows a push must already show it.
"""

from __future__ import annotations

import json
import os
import select
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.criteo import splitmix64  # noqa: E402


class KeySpace:
    """Rows [1, witness_lo) for the Zipf traffic; [witness_lo, num_keys)
    cut into private bands of ``witness_keys`` rows, one per (client, trip)."""

    def __init__(self, num_keys: int, clients: int, spec: dict, data: dict):
        self.w = int(spec["witness_keys"])
        self.trips_cap = int(spec["trips_cap"])
        self.witness_lo = num_keys - (clients + 1) * self.trips_cap * self.w
        self.vocab = float(sum(data["cat_vocab"]))
        self.s = float(data["zipf_s"])

    def zipf_rows(self, rng, n: int) -> np.ndarray:
        """Exactly ``n`` distinct rows, sorted."""
        e = 1.0 - self.s
        need, got = n, np.zeros(0, np.int64)
        while len(got) < n:
            u = rng.random(2 * need)
            ranks = np.floor((((self.vocab + 1.0) ** e - 1.0) * u + 1.0) ** (1.0 / e))
            rows = splitmix64(ranks.astype(np.uint64)) % np.uint64(self.witness_lo - 1) + np.uint64(1)
            got = np.union1d(got, rows.astype(np.int64))
            need = n - len(got) + 1024
        if len(got) > n:
            got = np.sort(rng.choice(got, n, replace=False))
        return got

    def witness(self, client: int, trip: int) -> np.ndarray:
        if trip >= self.trips_cap:
            raise RuntimeError(f"more than trips_cap={self.trips_cap} round trips: raise it in the traffic file")
        lo = self.witness_lo + ((client + 1) * self.trips_cap + trip) * self.w
        return np.arange(lo, lo + self.w, dtype=np.int64)


def prefix_pushes(rng, ks: KeySpace, spec: dict):
    """Client 0's single-client prefix: ``prefix_trips`` pushes of
    ``push_keys`` keys whose sets overlap by half, so rows are updated more
    than once."""
    n = int(spec["push_keys"])
    hot = ks.zipf_rows(rng, n // 2)
    for _ in range(int(spec["prefix_trips"])):
        keys = np.union1d(hot, ks.zipf_rows(rng, n))[:n]
        yield keys, (3.0 * rng.normal(size=n)).astype(np.float32)


def stop_requested() -> bool:
    r, _, _ = select.select([sys.stdin], [], [], 0)
    return bool(r) and sys.stdin.readline().strip() in ("STOP", "")


def main(job: dict) -> int:
    from parameter_server_tpu.parallel.multislice import ServerHandle
    from parameter_server_tpu.utils.config import PSConfig

    cid, spec = int(job["client"]), job["traffic"]
    ks = KeySpace(job["num_keys"], job["clients"], spec, job["data"])
    rng = np.random.default_rng([int(job["seed"]), 0xC11E, cid])
    h = ServerHandle(job["address"], 0, cid, PSConfig(), range_size=job["num_keys"])
    n_push, n_pull = int(spec["push_keys"]), int(spec["pull_keys"])
    w_of_g = one_step_weight(job["hyper"])
    out: dict = {}
    try:
        if cid == 0:
            # the single-client prefix: overlapping key sets, so rows are
            # updated more than once; every pulled row goes to the parent
            for r, (keys, grad) in enumerate(prefix_pushes(rng, ks, spec)):
                h.push(keys, grad)
                out[f"prefix_keys{r}"], out[f"prefix_grad{r}"] = keys, grad
                out[f"prefix_pull{r}"] = np.asarray(h.pull(keys[:n_pull])).ravel()
            print("PREFIX", flush=True)
        if sys.stdin.readline().strip() != "GO":
            return 2
        ops, wit_keys, wit_grad, unseen = [], [], [], 0
        trip, ready = 0, False
        warm = int(spec["warm_trips"])
        while True:
            w = ks.witness(cid, trip)
            keys = np.concatenate([ks.zipf_rows(rng, n_push - len(w)), w])
            grad = (3.0 * rng.normal(size=n_push)).astype(np.float32)
            grad[-len(w):] = np.where(np.abs(grad[-len(w):]) < 1.5, 2.0, grad[-len(w):])  # past l1
            pull_keys = np.concatenate([ks.zipf_rows(rng, n_pull - len(w)), w])
            t0 = time.perf_counter()
            h.push(keys, grad)
            t1 = time.perf_counter()
            got = np.asarray(h.pull(pull_keys)).ravel()
            t2 = time.perf_counter()
            ops += [(0, t0, t1, n_push), (1, t1, t2, n_pull)]
            wit_keys.append(w)
            wit_grad.append(grad[-len(w):])
            # an acknowledged push is visible to any later pull: one FTRL
            # step from zero at gradient g leaves z = g, n = g^2
            unseen += int(np.sum(~np.isclose(got[-len(w):], w_of_g(grad[-len(w):]), rtol=1e-5, atol=1e-7)))
            trip += 1
            if trip == warm and not ready:
                ready = True
                mean = float(np.mean([b[2] - a[1] for a, b in zip(ops[0::2], ops[1::2])]))
                print(f"READY {mean}", flush=True)
            if ready and stop_requested():
                break
        out.update(
            ops=np.asarray(ops, np.float64), witness_keys=np.concatenate(wit_keys),
            witness_grad=np.concatenate(wit_grad), unseen=np.int64(unseen), trips=np.int64(trip),
        )
    finally:
        h.close()
    np.savez(job["out"], **out)
    return 0


def one_step_weight(hyper: dict):
    """w after one FTRL step from zero at gradient g: z = g, n = g^2."""
    a, b, l1, l2 = (np.float32(hyper[k]) for k in ("alpha", "beta", "lambda_l1", "lambda_l2"))

    def w(g: np.ndarray) -> np.ndarray:
        g = g.astype(np.float32)
        shrunk = np.sign(g) * np.maximum(np.abs(g) - l1, np.float32(0))
        return -shrunk / ((b + np.abs(g)) / a + l2)

    return w


if __name__ == "__main__":
    sys.exit(main(json.loads(sys.argv[1])))
