"""chip_smoke.py's control flow on the CPU, and its refusal to run there.

The chip run is at Llama-3.2-1B widths; here the same two functions run at
``LlamaConfig.tiny()`` widths with the Pallas kernels in interpret mode, on
two of the virtual CPU devices (a dp=2 trainer mesh, a tp=2 engine)."""

import dataclasses
import os
import subprocess
import sys

import ray_tpu
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.kernels import force_kernel_backend

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import chip_smoke  # noqa: E402


def test_smoke_phases_run_on_cpu(cpu_mesh_devices):
    # A runtime an earlier test of this worker left up, with no TPU in it:
    # the smoke run must start its own and not be handed this one.
    ray_tpu.init(num_cpus=2)
    # vocab 512: the byte tokenizer (256 bytes + specials) must fit.
    cfg = chip_smoke.SmokeConfig(
        model=dataclasses.replace(LlamaConfig.tiny(), vocab_size=512),
        kernel_seq=128,
        train_batch=4, train_seq=128, train_steps=4, remat="attn",
        serve_slots=4, serve_max_seq=256, serve_dtype=None,
        prefill_chunk=64, long_prompt_chars=150, request_timeout_s=120.0)
    with force_kernel_backend("interpret"):
        out = chip_smoke.run_smoke(cfg, n_devices=2)
    train, serve = out["train"], out["serve"]
    assert set(out["kernels"]) == {"out", "dq", "dk", "dv", "dw",
                                   "rms_norm_424_rows", "kv_row_write",
                                   "decode_attention", "prefill_attention"}
    assert len(train["losses"]) == 4 and train["losses"][-1] < train["losses"][0]
    assert all(n > 0 for n in train["kernels"].values()), train["kernels"]
    assert len(train["memory"]) == 2
    assert serve["requests"] == 6
    assert serve["stats"]["device_failures"] == 0
    assert serve["stats"]["requests_failed"] == 0
    # The second identical request adopted the first one's prefix.
    assert serve["stats"]["prefix_hits"] >= 1


def test_chip_smoke_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO_ROOT,
                       env=env, capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "platform='cpu'" in r.stderr
