"""The step's two collectives' share of the interconnect, in %: the least
bytes a chip must receive a microstep for the pull and the push
(benchmark/bytes_model_mf_coll.py: the rows of its worker's keys that
another shard owns, and the other workers' gradients for the rows it owns,
counted from the keys the minibatches really hold, which the app leaves in
``config["observed"]``) over the device seconds under the two collectives'
scopes, over the chip's peak interconnect bits/s (all its links). A lower
bound over every link: it cannot pass 100%. None where the program names no
such scopes or the app counted no keys by owner."""

from benchmark import bytes_model_mf_coll
from benchmark.layer_metrics_coll import collective_ms


def read(run):
    owned = run["config"].get("observed", {}).get("keys_owned")
    times = [collective_ms(run, "ps.pull", "psum"), collective_ms(run, "ps.push", "all_gather")]
    if not owned or None in times or sum(times) <= 0:
        return None
    least = bytes_model_mf_coll.recv_bytes(owned, int(run["config"]["settings"]["rank"]))
    least_s = 8.0 * (least["pull"] + least["push"]) / run["peaks"]["ici_bits_per_s"]
    return 100.0 * least_s / (1e-3 * sum(times))
