"""CI contract tests (ISSUE 4 satellite; ISSUE 5 migrated them onto
pslint's DERIVED inventories): every counter bumped in code is visible
in the cluster dashboard, and every ``[server]``/``[wire]`` config key read
by code exists with a default in ``utils/config.py``.

The counter and config inventories are no longer regex lists maintained
here — they come from ``parameter_server_tpu.analysis.contracts``
(the same AST scan the ``counter-contract`` / ``config-contract``
checkers gate CI with), so the lists can never drift from the code.
"""

from __future__ import annotations

import dataclasses

from parameter_server_tpu.analysis import (
    config_key_usage,
    counter_inventory,
    load_package,
)

_INDEX = load_package()


class TestCounterContract:
    def test_every_literal_counter_reaches_format_cluster_stats(self):
        """Every counter name bumped via wire_counters.inc/observe_max/
        inc_many must appear in the ``cli stats`` dashboard output (the
        merged counter block prints every merged name — this breaks if
        someone filters it or renames a counter without the dashboard
        noticing). The inventory is DERIVED by pslint's AST scan;
        dynamic names (``fault_{action}``) are covered by their own
        chaos-stats path and are out of scope of the literal scan."""
        names = set(counter_inventory(_INDEX))
        # the tentpole counters must be part of the scanned inventory
        assert {
            "push_coalesced", "hdr_bytes_saved", "hdr_frames_bin",
            "wire_withheld_bytes_peak", "wire_window_shrinks",
            "wire_window_grows",
            # ISSUE 5: orphaned deferred replies consumed on conn death
            "rpc_deferred_orphaned",
            # ISSUE 7 serving plane: the serve_*/cache_* counters ride
            # the same derived inventory (and therefore the dashboard)
            "serve_cache_hits", "serve_cache_misses",
            "serve_cache_stale_hits", "serve_cache_validates",
            "serve_cache_invalidations", "serve_not_modified",
            "serve_shed", "serve_shed_served", "serve_encode_reuse",
            "serve_hot_keys", "coord_ingest_coalesced",
            # ISSUE 9 blackbox plane: boxes written + watchdog firings
            "blackbox_dumps", "watchdog_stalls",
        } <= names
        from parameter_server_tpu.utils.metrics import format_cluster_stats

        rep = {
            "nodes": {},
            "merged": {
                "counters": {n: 1 for n in names}, "hists": {}, "timers": {},
            },
        }
        out = format_cluster_stats(rep)
        missing = sorted(n for n in names if n not in out)
        assert not missing, f"counters invisible to cli stats: {missing}"

    def test_inventory_matches_the_ci_checker(self):
        """The checker that gates CI and the inventory this test uses
        are one code path — a counter passing here cannot fail there."""
        from parameter_server_tpu.analysis.contracts import (
            check_counter_contract,
        )

        assert check_counter_contract(_INDEX) == []

    def test_peak_counters_merge_as_max(self):
        """*_peak gauges (withheld bytes, inflight depth) must merge as a
        max cluster-wide — summing per-node peaks reports a depth nothing
        ever reached."""
        from parameter_server_tpu.utils.metrics import merge_telemetry

        m = merge_telemetry([
            {"counters": {"wire_withheld_bytes_peak": 100, "n": 1}},
            {"counters": {"wire_withheld_bytes_peak": 40, "n": 2}},
        ])
        assert m["counters"]["wire_withheld_bytes_peak"] == 100
        assert m["counters"]["n"] == 3


class TestConfigKeyContract:
    @staticmethod
    def _fields(cls) -> dict[str, bool]:
        out = {}
        for f in dataclasses.fields(cls):
            out[f.name] = (
                f.default is not dataclasses.MISSING
                or f.default_factory is not dataclasses.MISSING
            )
        return out

    def _check_section(self, section: str, cls) -> None:
        usage = config_key_usage(_INDEX)
        used = set(usage.get(section, {}))
        assert used, f"the [{section}] usage scan found nothing"
        fields = self._fields(cls)
        missing = sorted(used - set(fields))
        assert not missing, (
            f"[{section}] keys used without a default: {missing}"
        )
        assert all(fields.values())

    def test_every_used_wire_key_has_a_default(self):
        from parameter_server_tpu.utils.config import WireConfig

        self._check_section("wire", WireConfig)

    def test_every_used_server_key_has_a_default(self):
        from parameter_server_tpu.utils.config import ServerConfig

        self._check_section("server", ServerConfig)

    def test_every_used_serve_key_has_a_default(self):
        """ISSUE 7: every [serve] key the serving plane reads exists in
        ServeConfig with a default (derived, like [wire]/[server])."""
        from parameter_server_tpu.utils.config import ServeConfig

        self._check_section("serve", ServeConfig)

    def test_every_section_passes_the_ci_checker(self):
        """Beyond [wire]/[server]: the pslint checker covers EVERY
        config section's reads (data, solver, fault, trace, ...)."""
        from parameter_server_tpu.analysis.contracts import (
            check_config_contract,
        )

        assert check_config_contract(_INDEX) == []

    def test_server_section_loads_from_config_file(self, tmp_path):
        from parameter_server_tpu.utils.config import load_config

        p = tmp_path / "cfg.json"
        p.write_text(
            '{"server": {"apply_queue": 0, "max_batch": 7},'
            ' "wire": {"adaptive_window": true, "hdr_codec": "json"}}'
        )
        cfg = load_config(p)
        assert cfg.server.apply_queue == 0 and cfg.server.max_batch == 7
        assert cfg.wire.adaptive_window is True
        assert cfg.wire.hdr_codec == "json"
