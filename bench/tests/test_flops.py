"""The operation and byte counts against hand counts."""
import json

from bench.common import flops
from bench.tests.conftest import REPO

PHI3 = json.loads((REPO / "bench/configs/phi3-mini-3.8b/config.json").read_text())


def test_phi3_matmul_params_by_hand():
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192     # q k v o + gate up down
    assert flops.decoder_matmul_params(PHI3) == 32 * per_layer + 3072 * 32064
    assert flops.decoder_matmul_params(PHI3) == 3_722_379_264


def test_phi3_bytes_by_hand():
    assert flops.kv_bytes_per_token(PHI3) == 2 * 32 * 96 * 2 * 32 == 393_216
    # matrices, 65 norm scales and one embedding row, in bf16
    assert flops.decoder_weight_bytes(PHI3) == 2 * (3_722_379_264 + 66 * 3072)
    assert flops.decode_bytes(PHI3, 99) == 100 * 393_216


def test_phi3_token_and_prefill_flops():
    attn_per_key = 4 * 32 * 32 * 96
    assert flops.token_flops(PHI3, 0) == 2 * 3_722_379_264 + attn_per_key
    p = 128
    assert flops.prefill_flops(PHI3, p) == sum(flops.token_flops(PHI3, i)
                                               for i in range(p))


def test_traced_flops_count_prefill_once_and_each_new_token():
    from types import SimpleNamespace

    from bench.common import serving

    reqs = [SimpleNamespace(prompt_tokens=128), SimpleNamespace(prompt_tokens=256)]
    # request 0 starts inside the window (prefill, then tokens 1 and 2 at
    # positions 128 and 129); request 1 had 5 tokens and gains two more
    got = serving._traced_flops(PHI3, reqs, {1: 5}, {0: 3, 1: 7})
    want = (flops.prefill_flops(PHI3, 128) + flops.token_flops(PHI3, 128)
            + flops.token_flops(PHI3, 129) + flops.token_flops(PHI3, 260)
            + flops.token_flops(PHI3, 261))
    assert got == want
