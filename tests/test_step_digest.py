"""scripts/step_digest.py's digests of every cell's step programs at the
cells' real sizes, from shapes alone (nothing runs): that a change which
means to leave a cell's program alone left its jaxpr text alone. A PR that
means to change a cell's program replaces that cell's entry with ``python
scripts/step_digest.py``'s line for it, and says why beside it. The values
came here unchanged from tests/test_lfm2_moe.py (PR 43), where PR 37 first
pinned them. Five to six minutes for the ten cells on one worker."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script, and the benchmark's files that it imports
for path in (os.path.join(ROOT, "benchmarks"), os.path.join(ROOT, "scripts")):
    if path not in sys.path:
        sys.path.insert(0, path)
import step_digest                                   # noqa: E402

DIGESTS = {
    "gpt2m-fused-t1024": {
        "fused_step": "dbbd14c876ab61ff", "outputs": 1173, "equations": 14496,
        "state_and_loss_alone": "cf70de66025b38e8", "equations_alone": 14167},
    "gpt2m-party1-t1024": {
        "server_cross_entropy": "aad6c9a0bc376e66",
        "server_per_example_cross_entropy": "ecf490a5f0443f4f",
        "client_fwd": "0bc25e93e688c5fc", "client_bwd": "610f85cf7d919656"},
    "vitl16-fused-224": {
        "fused_step": "797e273ead5ed708", "outputs": 1176, "equations": 14316,
        "state_and_loss_alone": "d56c4febf0ce8ba7", "equations_alone": 13987},
    "vitl16-party-224": {
        "server_cross_entropy": "233e0d12f30900d2",
        "server_per_example_cross_entropy": "6e0f47fe2ae29a1a",
        "client_fwd": "c070cde753ba777a", "client_bwd": "709953683d33d6be"},
    # PR 41: the four routed cells' ladders have a third rung (a third branch
    # of every routed layer's two conditionals); the other five are unedited
    "trinity-mini-fused-t8192": {
        "fused_step": "492dddd8ecbad3f4", "outputs": 294, "equations": 3857,
        "state_and_loss_alone": "2a6e85528d47f27b", "equations_alone": 3784},
    "phi4flash-fused-t8192": {
        # PR 48: the Mamba layer's convolution and silu run as
        # ops/causal_conv.py's two kernels (09060d61752870bf /
        # cafee9375516a342 with the shifted sum, 2767 / 2750 equations)
        "fused_step": "6e231b8982fec7be", "outputs": 231, "equations": 2701,
        "state_and_loss_alone": "9f89872787eab6fe", "equations_alone": 2684},
    "joyai-flash-fused-t8192": {
        "fused_step": "4ed6ed722c498ecd", "outputs": 333, "equations": 4662,
        "state_and_loss_alone": "da37b672441bcd21", "equations_alone": 4560},
    "lfm2-moe-fused-t8192": {
        # PR 38: the grouped products take the 1536-wide experts whole
        # (e2cfe85da3c9f053 / 589fbae6853e320e with two rungs, 2215 / 2154)
        "fused_step": "b4d854ba89c9d50a", "outputs": 177, "equations": 2239,
        "state_and_loss_alone": "1c1de4ea8f557a4e", "equations_alone": 2166},
    "nemotronh-moe-fused-t8192": {
        # PR 42: the three Mamba-2 layers' recurrence runs as ops/ssd.py's
        # two kernels (9e01ca06e572914f / 0611112aef509c8a with the plain
        # form, 2511 / 2440 equations). PR 48: and their convolution and
        # silu as ops/causal_conv.py's two, with no jax.checkpoint around
        # them (c56e1e2fedbc254a / 659599157f1a1886 before, 2160 / 2098
        # equations); the eight others are unedited: none imports either file
        "fused_step": "0351a43695251779", "outputs": 180, "equations": 2088,
        "state_and_loss_alone": "5ab3029b10236749", "equations_alone": 2026},
    # PR 45: the looped cell, new; the nine above are unedited (the field
    # models/afmoe.py's attention gained is in no jaxpr)
    "ouro-loop-fused-t8192": {
        "fused_step": "084f5a013369332d", "outputs": 218, "equations": 5375,
        "state_and_loss_alone": "5bd3f216fe8cc99f", "equations_alone": 5303},
}


@pytest.mark.parametrize("cell", sorted(DIGESTS))
def test_the_cells_step_programs_are_the_ones_on_file(cell):
    assert step_digest.cell_digests(cell) == DIGESTS[cell]
