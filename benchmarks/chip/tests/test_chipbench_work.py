"""Operation and byte counts against counts worked by hand."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import spec, work

CHIP = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def mixtral():
    conf = json.loads((CHIP / "configs/mixtral-8x7b-l3.json").read_text())
    return spec.model_config(CHIP.parents[1], conf)


def test_dense_flops_per_token_layer(mixtral):
    # q,k,v: 2*4096*(32+8+8)*128; o: 2*4096*4096; router: 2*4096*8;
    # two experts of three 4096x14336 matrices: 2*3*2*4096*14336
    assert work.dense_flops_per_token_layer(mixtral) == (
        50_331_648 + 33_554_432 + 65_536 + 704_643_072)


def test_decode_token_flops(mixtral):
    # 3 layers of (788,594,688 + 4*32*128*100 scores) + 2*4096*32000 head
    assert work.decode_token_flops(mixtral, 100) == 2_632_843_264


def test_decode_gmm_reads_each_unique_expert_once(mixtral):
    flops, nbytes = work.decode_gmm(mixtral, unique_experts=2, rows=2)
    assert flops == 704_643_072
    assert nbytes == 2 * 352_321_536 + 2 * 2 * 4096 * 2
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.least_seconds(flops, nbytes, peak) == pytest.approx(
        704_675_840 / 819e9)                   # bandwidth-bound
