"""Config-4's REAL shape: dp=2 × pp=2 × sharding=2 × mp=2 — all four
axes >1 SIMULTANEOUSLY in one jitted program (reference: the GPT-1.3B
hybrid of Fleet dp+mp+pp + Sharding; SURVEY.md §2.4 config 4, §3.4;
VERDICT round-4 missing #3).

Needs 16 devices, so the 8-device suite mesh can't host it: the check
runs in its own sanitized 16-virtual-device CPU subprocess via
``__graft_entry__.py --config4``, which asserts loss AND grad parity
against the sequential single-device oracle plus that both the ZeRO-3
('sharding', input dim) and Megatron ('mp', output dim) weight shardings
actually took on the stacked block leaves."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_config4_four_axis_mesh_parity():
    sys.path.insert(0, REPO)
    from __graft_entry__ import _cpu_env

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         "--config4"],
        env=_cpu_env(16), cwd=REPO, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert "dryrun config4 OK: mesh=(dp=2, pp=2, sharding=2, mp=2)" \
        in proc.stdout, proc.stdout[-2000:]
