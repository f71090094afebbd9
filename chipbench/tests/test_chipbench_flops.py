"""Operation and byte counts against hand counts, and the configuration
files against the program's own parameter tree."""
import jax
import pytest

from chipbench import flops, spec
from chipbench.harness import model_config


def _config(name):
    """A configuration of the benchmark, or of the tests' own data."""
    data = spec.HERE / "tests" / "data"
    root = data if (data / "configs" / f"{name}.json").exists() else spec.HERE
    return spec.config(name, root)


# hand counts: (layer matmul weights, parameters in all, bytes in bf16)
HAND = {
    # q, k, v, o 4 x 4096^2 (32 KV heads); gate, up, down 3 x 4096 x 11008
    "qwen1.5-7b-l16": (4 * 4096 ** 2 + 3 * 4096 * 11008,
                       16 * (202375168 + 2 * 4096 + 3 * 4096)
                       + 2 * 151936 * 4096 + 4096, 8965988352),
    # q, o 2 x 4096^2; k, v 2 x 4096 x 1024 (8 KV heads); 3 x 4096 x 14336
    "mistral-7b-v0.3-l16": (2 * 4096 ** 2 + 2 * 4096 * 1024
                            + 3 * 4096 * 14336,
                            16 * (218103808 + 2 * 4096)
                            + 2 * 32768 * 4096 + 4096, 7516463104),
}
KV = {"qwen1.5-7b-l16": 256 * 1024, "mistral-7b-v0.3-l16": 64 * 1024}


@pytest.mark.parametrize("name", sorted(HAND))
def test_params_by_hand(name):
    m = _config(name)["model"]
    matmul, total, nbytes = HAND[name]
    assert flops.param_counts(m)["layer_matmul"] == matmul
    assert flops.total_params(m) == total
    assert 2 * flops.total_params(m) == nbytes
    assert flops.kv_bytes_per_token(m) == KV[name]


@pytest.mark.parametrize("name", sorted(HAND))
def test_params_match_the_program(name):
    """The program builds exactly as many parameters as the counts say."""
    from repro.models.registry import get_model
    conf = _config(name)
    model = get_model(model_config(conf))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == flops.total_params(conf["model"])
    assert model.kv_bytes_per_token() == flops.kv_bytes_per_token(
        conf["model"])


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_and_prefill_by_hand(name):
    m = _config(name)["model"]
    lm = HAND[name][0]
    attn = 4 * 16 * 32 * 128  # per key, all layers
    head = 2 * 4096 * m["vocab_size"]
    assert flops.decode_step_flops(m, [10, 300]) == (
        2 * (2 * 16 * lm + head) + attn * 310)
    n = 100
    assert flops.prefill_flops(m, [n]) == (
        n * 2 * 16 * lm + head + attn * n * (n + 1) // 2)
    assert flops.prefill_flops(m, [n, 0, 7]) == (
        (n + 7) * 2 * 16 * lm + 2 * head
        + attn * (n * (n + 1) + 7 * 8) // 2)


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_min_bytes(name):
    m = _config(name)["model"]
    total = HAND[name][2]
    embed = 2 * m["vocab_size"] * 4096
    got = flops.decode_step_min_bytes(m, [100, 200])
    assert got == total - embed + 2 * 2 * 4096 + 300 * KV[name]
