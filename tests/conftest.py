"""Test config: force JAX onto a virtual 8-device CPU platform.

This is the standard JAX trick for exercising multi-device semantics
(sharding, collectives, ring attention) without TPU hardware — the
substitute for the reference's missing fake-backend story (SURVEY §4).
Must run before the first `import jax` anywhere in the test process.
"""

import os

# Force CPU even if the environment pins JAX_PLATFORMS to a hardware
# backend: tests must be hermetic and multi-device (8 virtual CPUs).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Persistent XLA compile cache: the full tier is compile-dominated
# (~17 min serial on one core, mostly mesh/pipeline/neural compiles; a
# warm cache cuts e.g. test_moe 140 s → 84 s).  Where
# JAX_COMPILATION_CACHE_DIR is set it is left alone; otherwise the
# cache lives at a fixed path inside the (git-ignored) in-checkout
# cache directory the server uses, in a sub-directory keyed by CPU
# FEATURE FINGERPRINT: XLA's cache key is an HLO hash that excludes
# host machine features, so an XLA:CPU AOT artifact from a different
# microarchitecture (a copied checkout) would load and can SIGILL the
# suite.
def _jax_cache_dir() -> str:
    import hashlib

    try:
        with open("/proc/cpuinfo") as fh:
            flags = next(
                (ln for ln in fh if ln.startswith("flags")), ""
            )
    except OSError:
        import platform

        flags = platform.platform()
    fingerprint = hashlib.sha256(flags.encode()).hexdigest()[:12]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(repo, ".jax_cache", f"tests-cpu-{fingerprint}")


os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _jax_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

import pytest  # noqa: E402


def pytest_configure(config):
    # Two-tier suite (VERDICT r3 item 7): `pytest -m "not slow"` is the
    # fast tier — < 5 min on one core, still covering every route,
    # store, DSL, and engine path.  Compile-heavy modules (distributed
    # meshes, pipeline schedules, the neural fit surfaces, Pallas ops)
    # carry the slow marker and run in the full tier.
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy; excluded from the fast tier "
        "(pytest -m 'not slow')",
    )


@pytest.fixture()
def tmp_store(tmp_path):
    from learningorchestra_tpu.store import DocumentStore

    store = DocumentStore(tmp_path / "store")
    yield store
    store.close()


@pytest.fixture()
def artifacts(tmp_store):
    from learningorchestra_tpu.store import ArtifactStore

    return ArtifactStore(tmp_store)


@pytest.fixture()
def volumes(tmp_path):
    from learningorchestra_tpu.store import VolumeStorage

    return VolumeStorage(tmp_path / "volumes")
