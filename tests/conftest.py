"""Test harness: force an 8-device virtual CPU mesh before JAX imports.

This is the rebuild's analog of the reference's script/local.sh integration
harness (spawn scheduler + N servers + M workers as processes on one host):
multi-"node" logic runs on one host, with virtual devices standing in for
chips. The chip itself is exercised by chip_smoke.py and the benchmark
(BENCHMARK.json, benchmark/run.py).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the outer env may preset a TPU platform
# hermetic runs: cli.main / run_node place the persistent compile cache
# (hostenv.init_compile_cache); a test must not depend on what an earlier
# run left in it
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# a pytest plugin may have imported jax before this file ran, freezing its
# defaults from the earlier environment: set the same two through config
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8, (
    "tests must run on the 8-device virtual CPU mesh; got " + str(jax.devices())
)

import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    """Build ``native/libpsdata.so`` once, before a test imports it: here
    on the ``xdist`` controller, which configures before it starts a worker
    (or in the one process of a run without workers). ``make`` writes the
    library in place and ``data/native.py`` trusts any file not older than
    the source, so workers that each found none on a fresh checkout built
    it over one another, and one that loaded a half-written file parsed in
    Python for the rest of its life: its ``[native]`` tests failed."""
    if not hasattr(config, "workerinput"):
        from parameter_server_tpu.data import native

        native.native_available()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session", autouse=True)
def _lock_order_witness():
    """Arm the runtime lock-order witness (analysis/witness.py) for the
    whole tier-1 run: every lock the package constructs during tests is
    order-checked against the statically derived acquisition graph plus
    whatever orders the run itself witnesses. An inversion raises
    LockOrderViolation at the acquiring call site — a deterministic
    stack trace instead of a probabilistic deadlock hang in CI."""
    from parameter_server_tpu.analysis import witness

    witness.install()
    yield
    witness.uninstall()


#: thread-name prefixes exempt from the stray-thread check: stdlib /
#: third-party executor singletons (e.g. jax's compilation pools) that
#: legitimately outlive a test. Package-owned executors deliberately use
#: the "ps-" prefix so they can never hide here.
_THREAD_ALLOWLIST = ("ThreadPoolExecutor-",)

#: package-owned DAEMON service threads that must NOT outlive the test
#: that armed them (ISSUE 14 satellite): each has an owning close path
#: (Roller.close, MetricsServer.close, profiler.configure(0)) that the
#: arming code — including `cli train`'s finally block — is contracted
#: to run. Daemon-ness keeps them out of the general check above, so
#: they get their own: a survivor here means a leaked shutdown path,
#: exactly the bug class the idempotence tests pin.
_PS_OWNED_DAEMONS = ("ps-ts-roller", "ps-metrics", "ps-profiler")


@pytest.fixture(autouse=True)
def _no_stray_threads():
    """Fail any test that leaves non-daemon threads alive: a leaked
    thread is an unjoined executor or an unstopped server — it pins its
    captured state for the rest of the session and can deadlock
    interpreter shutdown. Daemon threads (the package's serving/reader
    threads are all daemonized by design) are out of scope, EXCEPT the
    package's own armable service threads (_PS_OWNED_DAEMONS), whose
    close paths are part of the live-ops contract."""
    # compare Thread OBJECTS, not idents: idents are documented as
    # recyclable after a thread exits, so a leaked thread could inherit
    # a recycled ident from the before-set and evade the check
    before = set(threading.enumerate())
    yield
    deadline = time.monotonic() + 5.0
    leaked: list[str] = []
    for t in threading.enumerate():
        if (
            t in before
            or t is threading.current_thread()
            or any(t.name.startswith(p) for p in _THREAD_ALLOWLIST)
        ):
            continue
        if t.daemon and not any(
            t.name.startswith(p) for p in _PS_OWNED_DAEMONS
        ):
            continue
        t.join(timeout=max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            leaked.append(t.name)
    if leaked:
        pytest.fail(
            f"test leaked live thread(s): {leaked} "
            "(join/stop/close them, or allowlist a deliberate singleton "
            "in tests/conftest.py)"
        )
