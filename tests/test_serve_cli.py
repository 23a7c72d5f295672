"""launch/serve.py CLI input validation: --mixed-lens must be rejected at
parse time with an actionable message, never deep in the engine."""
import pytest

from repro.launch.serve import parse_mixed_lens


def test_parse_mixed_lens_happy_path():
    assert parse_mixed_lens("16,64,24") == [16, 64, 24]
    assert parse_mixed_lens(" 8 , 9 ") == [8, 9]
    assert parse_mixed_lens(None) is None


@pytest.mark.parametrize("bad,msg", [
    ("16,,24", "empty entry"),
    (",16", "empty entry"),
    ("16,", "empty entry"),
    ("", "empty entry"),
    ("16,abc", "not an integer"),
    ("16,3.5", "not an integer"),
    ("0", "must be >= 1"),
    ("16,-4", "must be >= 1"),
])
def test_parse_mixed_lens_rejects_malformed(bad, msg):
    with pytest.raises(ValueError, match=msg):
        parse_mixed_lens(bad)


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py has no CPU fallback: without a TPU it exits non-zero
    and prints no result line."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
