"""Operation and byte counts against hand counts, and the weights the
benchmark makes against the program's own parameter layout, each taken
from the configuration's own module (``bench/reference/<reference>.py``)
as the harness takes them."""

import json
import os

import jax
import pytest

import flops
import run
import weights

CONF = os.path.join(run.BENCH, "configs")
CONFIGS = [c["name"] for c in run.load_benchmark()["configs"]]


def conf(name):
    with open(os.path.join(CONF, f"{name}.json")) as f:
        return json.load(f)


def module(cf):
    return run._reference(cf["reference"])


def tree(cf, seed=0):
    return jax.eval_shape(lambda: module(cf).make_weights(cf, seed))


@pytest.mark.parametrize("name,total,matmul", [
    # 40 x (2048*2048*2 + 2048*512*2 + 3*2048*8192 + 2*2048)
    #   + 2*49155*2048 + 2048
    ("granite_3_2b", 2_634_201_088, 2_533_365_760),
    # 12 x (3072*3072*2 + 3072*1024*2 + 3*3072*9216 + 2*3072)
    #   + 2*256000*3072 + 3072
    ("minitron_4b", 2_894_146_560, 2_107_637_760),
])
def test_param_counts_by_hand(name, total, matmul):
    cf = conf(name)
    arch = module(cf)
    s = arch.sizes(cf)
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree(cf))) == total
    # a step with no live context: 2 FLOPs and 2 bytes per matmul weight
    assert arch.decode_step_flops(s, [0]) == 2 * matmul
    assert arch.decode_step_bytes(s, [], 2) == 2 * matmul
    assert flops.param_counts(s)["total"] == total


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_match_the_program_layout(name):
    from repro.models import init_params
    cf = conf(name)
    ours = tree(cf)
    prog = jax.eval_shape(lambda k: init_params(run.model_config(cf), k),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(prog)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(prog)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_decode_step_counts_by_hand():
    cf = conf("granite_3_2b")
    arch = module(cf)
    s = arch.sizes(cf)
    # one row attending over 100 positions
    assert arch.decode_step_flops(s, [100]) == \
        2 * 2_533_365_760 + 4 * 40 * 32 * 64 * 100
    kv_per_pos = 2 * 40 * 8 * 64 * 2
    assert arch.decode_step_bytes(s, [100], 2) == \
        2 * (2_533_365_760 + 2048) + kv_per_pos * 101
    # rows add their own attention and cache, not more weights
    two = arch.decode_step_bytes(s, [100, 50], 2)
    assert two - arch.decode_step_bytes(s, [100], 2) == \
        2 * 2048 + kv_per_pos * 51


def small(name):
    cf = conf(name)
    cf.update(num_hidden_layers=1, hidden_size=64, num_attention_heads=2,
              num_key_value_heads=1, head_dim=32, intermediate_size=96,
              vocab_size=100)
    return cf


@pytest.mark.parametrize("name", ["granite_3_2b", "minitron_4b"])
def test_module_gives_the_benchmarks_weights_and_counts(name):
    # the dense configurations' module hands on bench/weights.py and
    # bench/flops.py: bitwise the same weights per seed, the same counts
    cf = small(name)
    arch = module(cf)
    a = arch.make_weights(cf, 2 ** 40 + 3)
    b = weights.make_weights(cf, 2 ** 40 + 3)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    assert all((x == y).all() for x, y in zip(jax.tree_util.tree_leaves(a),
                                              jax.tree_util.tree_leaves(b)))
    s = arch.sizes(cf)
    assert s == weights.sizes(cf)
    for lens in ([7], [1, 2, 3]):
        assert arch.decode_step_flops(s, lens) == \
            flops.decode_step_flops(s, lens)
        assert arch.decode_step_bytes(s, lens, 2) == \
            flops.decode_step_bytes(s, lens, 2)


def test_weights_are_seeded():
    cf = small("granite_3_2b")
    arch = module(cf)
    a = arch.make_weights(cf, 2 ** 40 + 3)
    b = arch.make_weights(cf, 2 ** 40 + 3)
    c = arch.make_weights(cf, 3)
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not (la[0] == lc[0]).all()
    std = float(a["layers"]["attn"]["wq"].std())
    assert 0.8 / 8 < std < 1.2 / 8           # 1/sqrt(64)
