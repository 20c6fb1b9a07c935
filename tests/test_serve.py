"""The serving entry point (``repro.launch.serve``) at reduced width under
both admission paths, and ``chip_smoke.py`` off the chip: it must refuse a
CPU, and its four-device disaggregated phase must hold on virtual CPU
devices."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch import serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def runs():
    base = ["--reduced", "--requests", "4", "--max-new", "6",
            "--max-batch", "4", "--max-len", "512"]
    mp = pytest.MonkeyPatch()
    mp.setattr(serve, "use_compile_cache", lambda: None)
    try:
        return {"whole": serve.main(base),
                "chunked": serve.main(base + ["--chunk-size", "48"])}
    finally:
        mp.undo()


def test_serve_both_admissions_finish_every_request(runs):
    for res in runs.values():
        assert res["device"]["platform"] == "cpu"
        assert res["width"] == "reduced" and res["requests"] == 4
        for r in res["done"]:
            assert len(r.tokens) == 6 and len(r.prompt) in serve.PROMPT_LENS
            assert np.isfinite(r.first_logits).all()
    # one prefill program per prompt length, all traced by the warm-up
    assert runs["whole"]["dispatch"] == {
        "flash_attention": {"ref": len(serve.PROMPT_LENS)},
        "paged_decode_attention": {"ref": 1}}
    assert runs["chunked"]["dispatch"] == {
        "paged_chunk_attention": {"ref": 1},
        "paged_decode_attention": {"ref": 1}}


def test_serve_whole_and_chunked_agree_bitwise(runs):
    """Live output projections make attention reach the logits, so this
    compares the two admission paths' KV and attention, not just the
    embedding of the last token."""
    for a, c in zip(runs["whole"]["done"], runs["chunked"]["done"]):
        assert np.array_equal(a.prompt, c.prompt)
        np.testing.assert_array_equal(a.first_logits, c.first_logits)
        np.testing.assert_array_equal(a.last_logits, c.last_logits)
        assert a.tokens == c.tokens


def _python(args, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_cpu():
    p = _python(["chip_smoke.py"], {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok": true' not in p.stdout


def test_chip_smoke_four_device_phase_on_virtual_devices():
    """The --four-chips phase, on four virtual CPU devices and a reduced
    model: workers pinned one per device, device-to-device handoffs,
    streams equal to the one-device oracle."""
    code = textwrap.dedent("""
        import jax, chip_smoke
        from repro.configs import get_reduced_config
        assert len(jax.devices()) == 4
        chip_smoke.four_chips(jax, get_reduced_config("gemma_2b"))
        print("FOUR_OK")
    """)
    p = _python(["-c", code], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.path.join(ROOT, "src") + os.pathsep + ROOT})
    assert p.returncode == 0, p.stdout + p.stderr
    assert "FOUR_OK" in p.stdout and "all device-to-device" in p.stdout
