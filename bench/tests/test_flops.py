"""Operation and byte counts against hand counts, and the weights the
benchmark makes against the program's own parameter layout."""

import json
import os

import jax
import pytest

import flops
import run
import weights

CONF = os.path.join(run.BENCH, "configs")


def conf(name):
    with open(os.path.join(CONF, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,total,matmul", [
    # 40 x (2048*2048*2 + 2048*512*2 + 3*2048*8192 + 2*2048)
    #   + 2*49155*2048 + 2048
    ("granite_3_2b", 2_634_201_088, 2_533_365_760),
    # 12 x (3072*3072*2 + 3072*1024*2 + 3*3072*9216 + 2*3072)
    #   + 2*256000*3072 + 3072
    ("minitron_4b", 2_894_146_560, 2_107_637_760),
])
def test_param_counts_by_hand(name, total, matmul):
    c = flops.param_counts(weights.sizes(conf(name)))
    assert c["total"] == total
    assert c["matmul"] == matmul


@pytest.mark.parametrize("name", ["granite_3_2b", "minitron_4b"])
def test_weights_match_the_program_layout(name):
    from repro.models import init_params
    cf = conf(name)
    s = weights.sizes(cf)
    ours = jax.eval_shape(weights._weight_program(*(s[k] for k in (
        "L", "d", "H", "KV", "hd", "F", "V"))), jax.numpy.zeros((2,),
                                                               "uint32"))
    prog = jax.eval_shape(lambda k: init_params(run.model_config(cf), k),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(prog)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(prog)):
        assert a.shape == b.shape and a.dtype == b.dtype
    n = sum(x.size for x in jax.tree_util.tree_leaves(ours))
    assert n == flops.param_counts(s)["total"]


def test_decode_step_counts_by_hand():
    s = weights.sizes(conf("granite_3_2b"))
    # one row attending over 100 positions
    assert flops.decode_step_flops(s, [100]) == \
        2 * 2_533_365_760 + 4 * 40 * 32 * 64 * 100
    kv_per_pos = 2 * 40 * 8 * 64 * 2
    assert flops.decode_step_bytes(s, [100], 2) == \
        2 * (2_533_365_760 + 2048) + kv_per_pos * 101
    # rows add their own attention and cache, not more weights
    two = flops.decode_step_bytes(s, [100, 50], 2)
    assert two - flops.decode_step_bytes(s, [100], 2) == \
        2 * 2048 + kv_per_pos * 51


def test_weights_are_seeded():
    cf = conf("granite_3_2b")
    cf.update(num_hidden_layers=1, hidden_size=64, num_attention_heads=2,
              num_key_value_heads=1, head_dim=32, intermediate_size=96,
              vocab_size=100)
    a = weights.make_weights(cf, 2 ** 40 + 3)
    b = weights.make_weights(cf, 2 ** 40 + 3)
    c = weights.make_weights(cf, 3)
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not (la[0] == lc[0]).all()
    std = float(a["layers"]["attn"]["wq"].std())
    assert 0.8 / 8 < std < 1.2 / 8           # 1/sqrt(64)
