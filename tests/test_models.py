"""Model-zoo shape/correctness tests (CPU, f32 to keep them cheap)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_default_matmul_precision", "highest")


def test_resnet50_forward_shape():
    # Shape-only via eval_shape: un-jitted eager execution of the 53-conv
    # graph costs minutes of per-op CPU compiles and proves nothing more
    # (numeric execution is covered by the train-step and bench paths).
    from horovod_tpu.models import ResNet50
    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda x: model.init(jax.random.PRNGKey(0), x, train=False), x)
    logits = jax.eval_shape(
        lambda v, x: model.apply(v, x, train=False), variables, x)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_resnet18_param_count():
    from horovod_tpu.models import ResNet18
    model = ResNet18(num_classes=1000, dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3)), train=False))
    n = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
    # torchvision resnet18 has 11.69M params; ours matches to within the
    # fc/in-shape differences.
    assert 11e6 < n < 12e6


def test_vgg16_param_count():
    from horovod_tpu.models import VGG16
    model = VGG16(num_classes=1000, dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3)), train=False))
    n = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
    assert abs(n - 138_357_544) < 1e5, n  # the canonical VGG-16 count


def test_inception_v3_shapes_and_params():
    from horovod_tpu.models import InceptionV3
    model = InceptionV3(num_classes=1000, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((2, 299, 299, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda x: model.init(jax.random.PRNGKey(0), x, train=False), x)
    n = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
    # Keras InceptionV3 (no aux head): 23,851,784 params.
    assert 23e6 < n < 25e6, n
    logits = jax.eval_shape(
        lambda v, x: model.apply(v, x, train=False), variables, x)
    assert logits.shape == (2, 1000)


def test_mnist_cnn_forward():
    from horovod_tpu.models import MnistCNN
    model = MnistCNN(dtype=jnp.float32)
    x = jnp.zeros((4, 28, 28, 1))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, x)
    assert logits.shape == (4, 10)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_word2vec_loss_and_shapes():
    from horovod_tpu.models import SkipGram
    model = SkipGram(vocab_size=100, embedding_dim=16)
    center = jnp.array([1, 2, 3], jnp.int32)
    context = jnp.array([4, 5, 6], jnp.int32)
    neg = jnp.array([7, 8, 9, 10], jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), center)
    emb = model.apply(variables, center)
    assert emb.shape == (3, 16)
    loss = model.apply(variables, center, context, neg,
                       method=SkipGram.nce_loss)
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_transformer_dense_forward():
    from horovod_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(vocab_size=128, num_layers=2, num_heads=4,
                            embed_dim=64, mlp_dim=128, dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = jax.jit(model.apply)(variables, tokens)
    assert logits.shape == (2, 16, 128)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_transformer_ring_matches_dense():
    """Sequence-sharded ring transformer == single-device dense
    transformer on the same weights — end-to-end SP correctness."""
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.models import Transformer, TransformerConfig

    base = dict(vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
                mlp_dim=64, dtype=jnp.float32)
    dense_model = Transformer(TransformerConfig(**base))
    ring_model = Transformer(TransformerConfig(attention="ring",
                                               sp_axis="sp", **base))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    variables = dense_model.init(jax.random.PRNGKey(0), tokens)
    expected = dense_model.apply(variables, tokens)

    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("sp",))
    positions = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32)[None],
                                 tokens.shape)

    def shard_fn(tokens, positions):
        return ring_model.apply(variables, tokens, positions)

    f = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))
    out = f(tokens, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


_POSITIONS = {
    # two documents packed into one row: the second starts again at 0
    "packed": lambda L: jnp.concatenate(
        [jnp.arange(L // 2 + 3), jnp.arange(L - L // 2 - 3)]),
    # every third position (a uniform SHIFT would prove nothing: rotary
    # scores read differences of positions)
    "stretched": lambda L: 3 * jnp.arange(L),
}


@pytest.mark.parametrize("positions", sorted(_POSITIONS))
@pytest.mark.parametrize("attention", ["flash", "ring", "ulysses"])
def test_transformer_reads_the_positions_it_is_given(attention, positions):
    """Positions that are NOT 0..L-1: every attention rotates by them, as
    the dense one does (rotary has one implementation, outside the kernels
    and the ring), and the answer is not the one at 0..L-1."""
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.models import Transformer, TransformerConfig

    n, L = 4, 32
    base = dict(vocab_size=64, num_layers=2, num_heads=4, embed_dim=32,
                mlp_dim=64, dtype=jnp.float32)
    dense_model = Transformer(TransformerConfig(**base))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, L), 0, 64)
    pos = jnp.broadcast_to(
        _POSITIONS[positions](L).astype(jnp.int32)[None], tokens.shape)
    variables = dense_model.init(jax.random.PRNGKey(0), tokens)
    expected = dense_model.apply(variables, tokens, pos)
    assert not np.allclose(np.asarray(expected),
                           np.asarray(dense_model.apply(variables, tokens)),
                           rtol=1e-2, atol=1e-2)

    sp = {} if attention == "flash" else {"sp_axis": "sp"}
    model = Transformer(TransformerConfig(attention=attention, **sp, **base))
    apply = lambda t, p: model.apply(variables, t, p)  # noqa: E731
    if sp:
        mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("sp",))
        apply = jax.shard_map(
            apply, mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(apply)(tokens, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_transformer_zigzag_ring_matches_dense(monkeypatch):
    """sp_schedule='zigzag' end-to-end: zigzag-shard tokens AND
    positions (rotary reads global positions, so any layout is exact),
    run the ring transformer, unshard, compare against the dense model
    on natural-order data. Kernel path via interpret mode; L=2048 over
    4 ranks -> 512/rank = two 256-token chunks."""
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import zigzag_shard, zigzag_unshard

    monkeypatch.setenv("HVD_TPU_PALLAS_INTERPRET", "1")
    n, L = 4, 2048
    base = dict(vocab_size=64, num_layers=2, num_heads=2, embed_dim=32,
                mlp_dim=64, dtype=jnp.float32, max_seq_len=L)
    dense_model = Transformer(TransformerConfig(**base))
    zz_model = Transformer(TransformerConfig(
        attention="ring", sp_axis="sp", sp_schedule="zigzag", **base))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, L), 0, 64)
    variables = dense_model.init(jax.random.PRNGKey(0), tokens[:, :16])
    expected = dense_model.apply(variables, tokens)

    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("sp",))
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 tokens.shape)
    tz = zigzag_shard(tokens, n)
    pz = zigzag_shard(positions, n)

    f = jax.jit(jax.shard_map(
        lambda t, p: zz_model.apply(variables, t, p),
        mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))
    out = zigzag_unshard(f(tz, pz), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)
