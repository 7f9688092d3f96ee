"""The served-token oracle at a tiny size: the float32 reference scores
the tokens the bf16 engine served; wrong pages, a lower precision and a
wrong token each show."""

import json
import os

import numpy as np
import pytest

from benchmarks.tests import helpers

SHUFFLED_PAGES = """
from bigdl_tpu.serving import cache as _c
_orig = _c.PagedKVCache.device_tables
def _shuffled(self, pages=None):
    # every slot reads the pages of another slot
    tables, lengths = _orig(self, pages=pages)
    return tables[::-1], lengths
_c.PagedKVCache.device_tables = _shuffled
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.make_copy(str(tmp_path_factory.mktemp("bench")))


def gap_lines(out):
    return {ln.split(":")[0].split()[1]: float(ln.split(":")[1].split()[0])
            for ln in out.splitlines() if ln.startswith("check served_gap")}


def test_bf16_engine_passes_and_a_shuffled_page_table_fails(copy):
    rc, good, out_good = helpers.rehearse(copy, "tiny_serve", seed=21,
                                          seconds=1.5)
    assert rc == 0 and good["correct"] is True, out_good
    rc, bad, out_bad = helpers.rehearse(copy, "tiny_serve", seed=21,
                                        seconds=1.5, before=SHUFFLED_PAGES)
    assert rc == 0 and bad["correct"] is False, out_bad
    assert gap_lines(out_bad)["served_gap_mean"] > \
        20 * max(gap_lines(out_good)["served_gap_mean"], 1e-3)


def test_reference_scores_its_own_greedy_tokens_at_zero_and_int8_above():
    """On tokens the float32 reference itself would serve, every gap is
    0; the tokens the int8 control puts first lie below them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gpt2_xl as ref

    with open(os.path.join(helpers.DATA, "tiny_gpt.json")) as fh:
        config = json.load(fh)
    sizes = ref.sizes_of(config)
    params = ref.init_params(2**31 + 9, sizes, jnp.bfloat16)
    assert params["h0"]["attn"]["wq"].dtype == jnp.bfloat16
    leaves = jax.tree.leaves(params)
    assert len(leaves) == 4 + 16 * sizes["n_layer"] + 1
    rng = np.random.default_rng(3)
    total8 = total32 = 0.0
    for _ in range(6):
        prompt = list(rng.integers(0, sizes["vocab"], 12))
        served = []
        for _ in range(10):   # greedy decoding by the reference itself
            x = ref.forward_hidden(params, sizes, prompt + served)
            _, first = ref._head_fn("float32")(
                params["ln_f"], params["head"]["weight"], x[-1:],
                jnp.zeros((1,), jnp.int32))
            served.append(int(first[0]))
        gaps, first = ref.served_gaps(params, sizes, prompt, served)
        assert (first == np.asarray(served)).all()
        assert float(np.max(gaps)) == 0.0
        _, first8 = ref.served_gaps(params, sizes, prompt, served, "int8")
        gaps8, _ = ref.served_gaps(params, sizes, prompt, served,
                                   score=first8)
        assert (gaps8 >= 0).all()
        total8 += float(np.sum(gaps8))
        wrong = [(t + 1) % sizes["vocab"] for t in served]
        gapsw, _ = ref.served_gaps(params, sizes, prompt, wrong)
        total32 += float(np.sum(gapsw))
    assert total32 > 10.0          # a wrong token lies far below the best
    assert total8 < total32        # int8 stays near the best, not on it
