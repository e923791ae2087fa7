"""Operations and bytes from shapes, against counts made by hand."""
import json
import os

from bench.flops import fleet
from bench.flops.kernels import pairwise_dist, rfa

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_pairwise_dist_by_hand():
    # 13 x 386 stack: 13*13*386 multiply-adds, 3 ops per distance entry;
    # reads 13*386 floats and writes 13*13
    assert pairwise_dist.ops_bytes(13, 386) == (
        2 * 65234 + 507, 4 * (5018 + 169))


def test_rfa_by_hand():
    # 7 bucket means of 386: Gram 2*49*386, 32 steps of 2*49 + 8*7,
    # combine 2*7*386; reads the stack twice, writes 386 floats
    ops, nbytes = rfa.ops_bytes(7, 386, 32)
    assert ops == 37828 + 32 * 154 + 5404
    assert nbytes == 4 * (2 * 2702 + 386)


def test_policy_forward_and_fleet_step_by_hand():
    cfg = config("decbyzpg-cartpole-k13")
    # 4 -> 16 -> 16 -> 2: 64 + 256 + 32 multiply-adds
    assert fleet.forward_flops(cfg) == 704
    # large step: 13 agents x 20 episodes x 100 steps x 3 passes
    assert fleet.policy_flops(cfg, 1, 0) == 13 * 20 * 100 * 3 * 704
    # small step: 4 episodes, 3 passes plus 4 for the importance term
    assert fleet.policy_flops(cfg, 0, 1) == 13 * 4 * 100 * 7 * 704


def test_kernel_calls_follow_the_configuration():
    assert fleet.n_params(config("decbyzpg-cartpole-k13")) == 386
    rfa = fleet.kernel_calls(config("decbyzpg-cartpole-k13"))
    # buckets of 2 (Lemma 3: floor(0.5 / (3/13))) over 13 agents -> 7
    assert rfa["rfa"] == [(13, (7, 386, 32))]
    # the diameter once, and 13 agents' MDA in each of 4 rounds
    assert rfa["pairwise_dist"] == [(1, (13, 386)), (52, (13, 386))]
    mean = fleet.kernel_calls(config("dec-page-pg-cartpole-k13"))
    assert mean == {"pairwise_dist": [(1, (13, 386))]}


def test_pallas_calls_are_told_apart_by_their_outermost_wrapper():
    from bench.kernel_ops import classify_hlo
    call = ('  %{} = f32[8,128]{{1,0}} custom-call(f32[8,512]{{1,0}} %p), '
            'custom_call_target="tpu_custom_call", metadata={{op_name="{}" '
            'stack_frame_id=1}}')
    text = "\n".join([
        call.format("gram.26", "jit(one)/vmap()/while/body/closed_call/"
                    "vmap(jit(rfa_pallas))/jit(gram)/pallas_call"),
        call.format("vmap_jit_rfa_pallas__.27", "jit(one)/vmap()/"
                    "vmap(jit(rfa_pallas))/pallas_call"),
        call.format("gram.27", "jit(one)/vmap()/while/body/closed_call/"
                    "jit(gram)/pallas_call"),
        call.format("custom-call.3", "jit(one)/jit(unknown_fn)/pallas_call"),
        "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    ])
    assert classify_hlo(text) == {
        "gram.26": "rfa", "vmap_jit_rfa_pallas__.27": "rfa",
        "gram.27": "pairwise_dist", "custom-call.3": "other"}
