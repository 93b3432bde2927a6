"""Test harness config: run JAX on a virtual 8-device CPU mesh.

This is the TPU-world analog of "multi-node on one box" (SURVEY.md §4):
sharding/collective code paths are exercised for real, just on host CPU.
Must run before jax initializes its backends, hence env vars at import time.
"""

import os

# The suite runs on the CPU whatever the caller's environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA-executable cache: OFF by default. It used to shave
# minutes off reruns, but on this jaxlib deserializing a cached CPU
# executable mid-suite SEGFAULTS the whole pytest process (reproduced
# deterministically: suite dies at the first test that gets a cache hit
# after enough prior compile state accumulates; passes start-to-finish
# with the cache disabled). Opt back in with RAFIKI_TEST_COMPILE_CACHE=1
# on a jax build where the cache is sound; the dir is keyed by jaxlib
# version so executables never cross versions.
if os.environ.get("RAFIKI_TEST_COMPILE_CACHE", "") == "1":
    import jaxlib

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(os.path.dirname(os.path.dirname(
                          os.path.abspath(__file__))), ".jax_cache",
                          getattr(jaxlib, "__version__", "unknown")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
else:
    jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture()
def tmp_workdir(tmp_path):
    return tmp_path


@pytest.fixture(scope="session")
def trained_lm(tmp_path_factory):
    """ONE tiny trained LM shared by every serving-side test file
    (decode engine, draft speculation, kv-int8, multi-adapter, paged
    KV, streaming) — previously each file's module-scoped copy re-ran
    the same training, ~5s a pop on the default leg. Tests treat it as
    read-only: engines and dumps never mutate ``_params``."""
    from test_decode_engine import KNOBS

    from rafiki_tpu.data import generate_text_classification_dataset
    from rafiki_tpu.models.llama_lora import LlamaLoRA

    d = tmp_path_factory.mktemp("lm_shared")
    tr = str(d / "train.jsonl")
    generate_text_classification_dataset(tr, 64, seed=0)
    m = LlamaLoRA(**KNOBS)
    m.train(tr)
    return m


@pytest.fixture(scope="session")
def trained(trained_lm):
    """Short name most serving tests use; ``trained_lm`` exists for
    files whose own module-level ``trained`` fixture shadows this one
    (e.g. test_worker_serving's sub-train-job fixture)."""
    return trained_lm
