"""ffcompile.sh — app launcher generation (reference ffcompile.sh:1-7
builds one binary per app; here it emits a cache-pinning launcher and
builds the native components)."""

import os
import stat
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ffcompile_emits_launcher(tmp_path):
    out = tmp_path / "alexnet_launcher"
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "ffcompile.sh"), "alexnet", str(out)],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert out.exists()
    assert os.stat(out).st_mode & stat.S_IXUSR
    body = out.read_text()
    assert "flexflow_tpu.apps.alexnet" in body
    assert "JAX_COMPILATION_CACHE_DIR" in body
    # Native components were (re)built.
    for lib in ("_ffsim.so", "_ffproto.so", "_ffdata.so"):
        assert os.path.exists(
            os.path.join(REPO, "flexflow_tpu", "native", lib)
        )


def test_ffcompile_rejects_unknown_app(tmp_path):
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "ffcompile.sh"), "nosuchapp",
         str(tmp_path / "x")],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode != 0
    assert "unknown app" in proc.stderr
