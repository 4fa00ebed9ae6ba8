"""`embedding_time_share.train` on a token cell's `scopes` table (the
scoped trace of `deepseek_v2_fit` that `PERF.md` section 5 has, PR 47, ms
a step), on the image net's window recorded on a v5e
(`data/tiny_tpu_scoped.*`) and on a run with no trace; which cells list
it. CPU, no device number."""

import json
import os

import pytest

from benchmarks import harness, run as bench_run, span_reduce, xplane_schema

DATA = os.path.join(os.path.dirname(__file__), "data")
TOKEN_CELLS = ["trinity_large_fit", "minicpm_sala_fit", "deepseek_v2_fit",
               "granite_4_0_h_small_fit", "ouro_2_6b_fit"]


def _read(facts):
    return harness.load_module(
        "layer_metrics", "embedding_time_share.train.py").read(facts)


def _deepseek_scopes(embedding_backward_ms):
    """The step by first scope, forward and backward, with the embedding's
    backward as given: 23.3 where XLA's scatter-add ran."""
    row = lambda fwd, bwd: {"s": (fwd + bwd) / 1e3, "forward_s": fwd / 1e3,
                            "backward_s": bwd / 1e3, "n": 10}
    return {
        "layer0_embeddingsequencelayer": row(0.5, embedding_backward_ms),
        "layer1_latenttransformerblock": row(30.4, 88.1),
        **{f"layer{i}_latenttransformerblock": row(22.0, 60.4)
           for i in range(2, 6)},
        "layer7_rnnoutputlayer/loss": row(6.7, 16.7),
        "updater": row(17.3, 0.0), None: row(6.8, 0.0)}


@pytest.mark.parametrize("backward_ms, share", [(23.3, 4.58), (1.5, 0.40)],
                         ids=["xlas_scatter", "grouped_product"])
def test_a_token_cell_reads_the_first_layers_share(backward_ms, share):
    scopes = _deepseek_scopes(backward_ms)
    total = sum(r["s"] for r in scopes.values())
    got = _read({"trace": {}, "scopes": scopes})
    assert got == pytest.approx(100.0 * (0.5 + backward_ms) / 1e3 / total)
    assert got == pytest.approx(share, abs=0.01)


def test_an_image_cell_and_an_untraced_run_read_nothing():
    space = xplane_schema.read_xspace(
        os.path.join(DATA, "tiny_tpu_scoped.xplane.pb"))
    scopes = span_reduce.by_scope(space)
    assert "stem_conv" in scopes and "updater" in scopes
    assert _read({"trace": {}, "scopes": scopes}) is None
    assert _read({"trace": None, "scopes": None}) is None
    assert _read({"trace": {}, "scopes": {}}) is None
    # a net whose FIRST layer is another: an embedding further down is not
    # "the first layer's scope"
    other = {"layer0_denselayer": {"s": 1.0, "n": 3},
             "layer1_embeddingsequencelayer": {"s": 1.0, "n": 3}}
    assert _read({"trace": {}, "scopes": other}) is None


def test_the_five_token_cells_list_it_and_no_other():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    entry = spec["per_layer"][-1]
    assert entry == {
        "name": "embedding_time_share.train", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "layers and kernels",
        "moves": "train_items_per_s", "workloads": TOKEN_CELLS}
    for cell in spec["workloads"]:
        names = {m["name"] for m in
                 bench_run.load_cell(spec, cell["name"])["per_layer"]}
        assert ("embedding_time_share.train" in names) == (
            cell["name"] in TOKEN_CELLS)
