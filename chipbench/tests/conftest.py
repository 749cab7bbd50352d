import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four host devices, as one v5e-4 host has chips: the four-chip cells'
# mesh runs on them (set before the first jax import)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4"
                               ).strip()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import pytest  # noqa: E402


@pytest.fixture
def candidate_root(tmp_path, monkeypatch):
    """A copy of the benchmark that holds ``candidate.CELL``, and ``spec``
    reading its pieces from there until the test ends."""
    import candidate
    import spec
    monkeypatch.setattr(spec, "HERE", spec.HERE)
    monkeypatch.setattr(spec, "ROOT", spec.ROOT)
    candidate.copy(str(tmp_path))
    candidate.add(str(tmp_path))
    spec.use_root(str(tmp_path))
    return str(tmp_path)
