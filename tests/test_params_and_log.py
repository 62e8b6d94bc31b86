import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafiki_tpu.sdk.log import ModelLogger, parse_logs
from rafiki_tpu.sdk.params import dump_params, load_params


def test_params_roundtrip_numpy_and_jax():
    params = {
        "dense": {"w": np.ones((4, 3), np.float32), "b": jnp.zeros((3,))},
        "scale": 2.5,
        "meta": {"classes": [0, 1, 2], "name": "m"},
    }
    data = dump_params(params)
    assert isinstance(data, bytes)
    out = load_params(data)
    np.testing.assert_array_equal(out["dense"]["w"], params["dense"]["w"])
    np.testing.assert_array_equal(out["dense"]["b"], np.zeros((3,)))
    assert out["scale"] == 2.5
    assert out["meta"]["name"] == "m"


def test_logger_sink_and_parse():
    lines = []
    lg = ModelLogger()
    lg.set_sink(lines.append)
    lg.define_plot("loss curve", ["loss"], x_axis="epoch")
    lg.log("starting")
    lg.log(loss=1.5, epoch=0)
    lg.log(loss=0.5, epoch=1)
    parsed = parse_logs(lines)
    assert parsed["messages"][0]["message"] == "starting"
    assert [m["loss"] for m in parsed["metrics"]] == [1.5, 0.5]
    assert parsed["plots"][0]["title"] == "loss curve"
    assert parsed["plots"][0]["x_axis"] == "epoch"


def test_parse_logs_tolerates_plain_lines():
    parsed = parse_logs(["not json at all"])
    assert parsed["messages"][0]["message"] == "not json at all"


# ---------------------------------------------------------------------------
# the stream: flax's msgpack bytes, built from views of the leaves' memory
# ---------------------------------------------------------------------------

def _rand(shape, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


#: name -> (tree factory, the array bytes the stream has to copy)
_STREAM_CASES = {
    "f32": (lambda: {"w": _rand((4, 3)), "b": _rand((3,))}, 0),
    "bfloat16": (lambda: {"w": _rand((5, 7), _bf16())}, 0),
    "int32_and_bool": (lambda: {"i": np.arange(12, dtype=np.int32),
                                "m": np.array([[True, False]])}, 0),
    "zero_d_and_numpy_scalar": (lambda: {"z": np.array(3.0, np.float32),
                                         "s": np.float32(2.5),
                                         "n": np.int64(7)}, 0),
    "empty": (lambda: {"e": np.zeros((0, 3), np.float32)}, 0),
    "fixext16": (lambda: {"i": np.arange(6, dtype=np.int8)}, 0),
    "transposed": (lambda: {"t": _rand((6, 4)).T, "w": _rand((2, 2))},
                   6 * 4 * 4),
    "unsorted_nested_keys": (
        lambda: {"z": {"b": _rand((2,)), "a": {"y": _rand((3,)), "x": 1}},
                 "a": {"k": _rand((1,))},
                 **{f"k{i}": i for i in range(20)}}, 0),
    "list_and_plain_scalars": (
        lambda: {"classes": [0, 1, 2], "name": "m", "scale": 2.5,
                 "flag": True, "none": None, "c": 1 + 2j,
                 "nested": {"empty": {}}}, 0),
    "arrays_in_a_list": (lambda: {"l": [_rand((3,)), _rand((2, 2))]},
                         3 * 4 + 4 * 4),
    "jax_array": (lambda: {"j": jnp.arange(6.0).reshape(2, 3),
                           "l": [jnp.ones(2)]}, 2 * 4),
    "root_array": (lambda: _rand((3, 3)), 0),
    "complex_array": (lambda: {"c": _rand((4,)).astype(np.complex64)}, 0),
    # MAX_CHUNK_SIZE is patched to 1000 bytes in every case: these exceed it
    "chunked": (lambda: {"big": _rand((30, 30)), "small": _rand((10,)),
                         "odd": np.arange(333, dtype=np.int16)
                         .astype(np.float64)}, 0),
    "chunked_transposed": (lambda: {"big": _rand((30, 20)).T},
                           30 * 20 * 4),
    "chunked_root": (lambda: _rand((1001,), _bf16()), 0),
}


@pytest.mark.parametrize("case", sorted(_STREAM_CASES))
def test_stream_equals_flax_msgpack_byte_for_byte(case, monkeypatch):
    """`stream_params`'s buffers joined are `msgpack_serialize(tree)`: the
    format on disk did not change, every reader reads it as before; the
    stream copies only what it has to."""
    from flax import serialization

    from rafiki_tpu.sdk.params import stream_params

    monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", 1000)
    make, copied_bytes = _STREAM_CASES[case]
    tree = make()
    buffers, copied = stream_params(tree)
    joined = b"".join(buffers)
    assert joined == serialization.msgpack_serialize(tree)
    assert dump_params(tree) == joined
    assert copied == copied_bytes
    # the big pieces are views, not bytes made here
    assert sum(len(b) for b in buffers if isinstance(b, bytes)) + sum(
        b.nbytes for b in buffers if isinstance(b, memoryview)) == len(joined)
    back = load_params(joined)
    want = jax.tree_util.tree_leaves(tree)
    got = jax.tree_util.tree_leaves(back)
    assert len(got) == len(want)
    for w, g in zip(want, got):
        if isinstance(w, (np.ndarray, np.generic, jax.Array)):
            assert np.asarray(g).dtype == np.asarray(w).dtype
            assert np.asarray(g).shape == np.asarray(w).shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            assert g == w


def test_stream_views_share_the_leaves_memory():
    """No `tobytes()`: a contiguous leaf's buffer IS the leaf's memory."""
    from rafiki_tpu.sdk.params import stream_params

    w = _rand((64, 64))
    buffers, copied = stream_params({"w": w})
    views = [b for b in buffers if isinstance(b, memoryview)]
    assert copied == 0 and len(views) == 1
    assert np.shares_memory(np.frombuffer(views[0], np.uint8), w)


def test_stream_refuses_what_flax_refuses():
    from rafiki_tpu.sdk.params import stream_params

    with pytest.raises(ValueError, match="Object and structured dtypes"):
        stream_params({"o": np.array([object()], dtype=object)})
