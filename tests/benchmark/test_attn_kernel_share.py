"""``model.attn_kernel_share``: the fused attention kernels' share of the
``attn`` scope's device time, on a hand-made compiled module and trace with
known answers."""

import pytest

from benchharness import load

S = "jit(step)/shard_map"

# attention's projection (a fusion), its forward kernel and, in the
# backward, its dq kernel, both Pallas custom calls under ``attn/flash_*``;
# a custom call that is not one of them (an MLP kernel); an MLP product
FUSED = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  ROOT %multiply.1 = f32[8]{{0}} multiply(%x, %x), metadata={{op_name="{S}/jvp()/while/body/attn/dot_general"}}
}}

ENTRY %main.2 (z: f32[8]) -> f32[8] {{
  %z = f32[8]{{0}} parameter(0)
  %fusion.3 = f32[8]{{0}} fusion(%z), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{S}/jvp()/while/body/attn/dot_general"}}
  %flash_fwd.4 = (f32[8]{{0}}, f32[8]{{0}}) custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={{op_name="{S}/jvp()/while/body/attn/flash_fwd/pallas_call"}}, backend_config={{"custom_call_config": {{"body": "TUxJUgA="}}}}
  %flash_dq.5 = f32[8]{{0}} custom-call(%z), custom_call_target="tpu_custom_call", metadata={{op_name="{S}/transpose(jvp())/while/body/checkpoint/attn/flash_dq/pallas_call"}}
  %matmul.6 = f32[8]{{0}} custom-call(%z), custom_call_target="tpu_custom_call", metadata={{op_name="{S}/jvp()/while/body/mlp/matmul/pallas_call"}}
  ROOT %fusion.7 = f32[8]{{0}} fusion(%matmul.6), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{S}/jvp()/while/body/mlp/dot_general"}}
}}
"""

# the same step with attention as compiler-made ops only
UNFUSED = "\n".join(line for line in FUSED.splitlines()
                    if "flash_" not in line)


def hand_made():
    """Window [0, 100]: the projection 10 ns, the forward kernel 30 (5 of
    them before the window), the dq kernel 20, the MLP kernel 15 and the
    MLP product 25."""
    ops = [
        ["fusion.3", "fusion", "f32[8]", 0, 10],
        ["flash_fwd.4", "flash_fwd", "tuple", -5, 25],
        ["flash_dq.5", "flash_dq", "f32[8]", 25, 45],
        ["matmul.6", "matmul", "f32[8]", 45, 60],
        ["fusion.7", "fusion", "f32[8]", 60, 85],
    ]
    return {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
            "host": [["bench.window", 0, 100]], "fused": {}}


class _View:
    def __init__(self, hlo, trace):
        self.raw = {"hlo_text": hlo, "steps": 5}
        self.trace = trace
        self.cell = {"config": {"mesh": {"data": 1, "model": 1}}}


@pytest.fixture
def reader(monkeypatch):
    """The reader over a stand-in for the traced run's profile:
    ``trace.extract`` gives the hand-made trace.  It imports
    ``benchmark.scopes``, as under ``run.py``."""
    import benchmark.scopes as sc
    tr = sc._trace_module()
    monkeypatch.setattr(tr, "latest_xplane", lambda d: "profile.xplane.pb")
    monkeypatch.setattr(tr, "extract", lambda path, hlo: hand_made())
    monkeypatch.setattr(sc.measure, "cache", None, raising=False)
    return load("benchmark/metrics/model.attn_kernel_share.py")


def test_kernels_are_the_attention_custom_calls(reader):
    assert reader.kernels(FUSED) == {"flash_fwd.4", "flash_dq.5"}
    assert reader.kernels(UNFUSED) == set()


def test_share_of_attention_time(reader):
    """attn: the projection 10, the kernels 25 (clipped) + 20; the MLP
    kernel is no attention kernel."""
    got = reader.read(_View(FUSED, {"busiest": "/device:TPU:0"}))
    assert got == pytest.approx(100 * 45 / 55)


def test_zero_without_a_kernel_under_attn(reader):
    """Attention as compiler-made ops (the program before the kernels):
    the projection is all of ``attn``, none of it in a kernel."""
    import benchmark.scopes as sc
    sc.measure.cache = None
    assert reader.read(_View(UNFUSED, {"busiest": "/device:TPU:0"})) == 0.0


def test_nothing_without_a_trace(reader):
    assert reader.read(_View(FUSED, None)) is None
