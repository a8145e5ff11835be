"""chip_smoke.py's control flow, rehearsed on the CPU at --tiny sizes.

The script is the quickest proof that the system still starts on a chip;
these tests are the proof that the script itself still runs: every phase
in order, the last line in the shape the driver reads, and no pass
without an accelerator unless --tiny asked for a rehearsal. They run the
script in the test's own process on the CPU backend the suite already
has (kernels interpreted), so they say nothing about the chip.
"""

import json

import pytest

import chip_smoke
from distributed_model_parallel_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _unbind_span_sink():
    """The trainers bind their telemetry run as this thread's span sink;
    don't leave the last one bound for whichever test runs next."""
    yield
    tracing.uninstall()


def _last_line(out: str) -> dict:
    result = json.loads(out.strip().splitlines()[-1])
    assert result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    # a rehearsal reports the backend it ran on, never "tpu"
    assert result["device"]["platform"] == "cpu"
    return result


def test_tiny_rehearsal_runs_every_phase(capsys):
    assert chip_smoke.main(["--tiny"]) == 0
    out = capsys.readouterr().out
    for name in ("cnn", "lm", "serve", "serve-routed", "serve-hybrid",
                 "serve-looped"):
        assert f"phase {name}: ok" in out, out[-2000:]
    assert out.index("phase cnn: ok") < out.index("phase lm: ok") \
        < out.index("phase serve: ok") < out.index("phase serve-routed: ok") \
        < out.index("phase serve-hybrid: ok") \
        < out.index("phase serve-looped: ok")
    for what in ("gated-delta decode kernel vs the step",
                 "gated-delta chunked form (80 tokens) vs the recurrence",
                 "f32 hybrid engine: greedy tokens identical",
                 "9 cache layers for 3 layers of weights",
                 "f32 looped engine: greedy tokens identical",
                 "looped decode logits through 9 cache layers, kernel vs "
                 "xla"):
        assert f"ok: {what}" in out, out[-3000:]
    assert "multichip" not in out
    _last_line(out)


def test_refused_without_accelerator(capsys):
    """Without --tiny a CPU backend is refused before any phase, even
    one the caller asked for with JAX_PLATFORMS=cpu."""
    for argv in ([], ["--multichip"]):
        assert chip_smoke.main(argv) != 0
        captured = capsys.readouterr()
        assert captured.out == ""
        assert "no usable accelerator" in captured.err


def test_tiny_multichip_rehearsal_runs_only_cross_chip_phases(capsys):
    """--multichip on four of the suite's eight virtual devices."""
    assert chip_smoke.main(["--tiny", "--multichip"]) == 0
    out = capsys.readouterr().out
    for name in ("multichip-cnn", "multichip-lm"):
        assert f"phase {name}: ok" in out, out[-2000:]
    for single in ("phase cnn:", "phase lm:", "phase serve:"):
        assert single not in out
    for path in ("GSPMD dp4", "shard_map DDP dp4", "four-stage pipeline",
                 "LM dp2 x tp2", "LM pp2 1F1B"):
        assert f"ok: {path}" in out
    assert _last_line(out)["device"]["count"] >= 4
