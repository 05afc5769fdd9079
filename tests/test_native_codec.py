"""Native C++ msgpack codec: build, wire compatibility with
python-msgpack in both directions, fuzzed roundtrips, RPC integration.
"""

import os
import shutil
import subprocess
import sys

import msgpack
import pytest

from nomad_tpu.native import load_codec

native = load_codec()

# a machine without a compiler runs the msgpack path and skips this
# file; with one, a loader that gives None is a fault of the program
pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++: the msgpack path")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_a_compiler_means_the_codec_loads():
    assert native is not None, (
        "g++ is here and load_codec() gave None: the build or its "
        "self-check failed (the nomad_tpu.native warning says which)")


def test_six_processes_on_an_empty_cache_all_get_the_module(tmp_path):
    """Two agents, or six test workers, first started on a fresh
    install: each builds for itself and every one loads it."""
    code = ("from nomad_tpu.native import load_codec\n"
            "print(load_codec() is not None)\n")
    env = {**os.environ, "NOMAD_TPU_NATIVE_CACHE": str(tmp_path)}
    env.pop("NOMAD_TPU_NATIVE", None)
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(6)]
    outs = [p.communicate(timeout=300) for p in procs]
    assert [o.strip() for o, _e in outs] == ["True"] * 6, outs
    # what is left is the module, no process's temp file
    assert [n.split("-")[0] for n in os.listdir(tmp_path)] == [
        "nomad_tpu_native_codec"]


CASES = [
    None, True, False,
    0, 1, 127, 128, 255, 256, 65535, 65536, 2**31 - 1, 2**31,
    2**63 - 1, 2**64 - 1,
    -1, -32, -33, -128, -129, -32768, -32769, -2**31, -2**31 - 1, -2**63,
    0.0, 2.5, -1e300,
    "", "hello", "x" * 31, "x" * 32, "x" * 255, "x" * 70000, "uni-é漢",
    b"", b"\x00\xff", b"y" * 300,
    [], [1, 2, 3], list(range(20)), [[1], [2, [3]]],
    {}, {"a": 1}, {str(i): i for i in range(20)},
    [1, "two", 3.0, None, True, b"x", {"k": [1, 2]}],
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: repr(c)[:40])
def test_roundtrip_and_cross_compat(case):
    enc = native.packb(case)
    # our bytes decode with python-msgpack
    assert msgpack.unpackb(enc, raw=False, strict_map_key=False) == case
    # python-msgpack bytes decode with us
    ref = msgpack.packb(case, use_bin_type=True)
    assert native.unpackb(ref) == case
    # self roundtrip
    assert native.unpackb(enc) == case


def test_tuple_encodes_as_array():
    assert native.unpackb(native.packb((1, 2))) == [1, 2]


def test_errors():
    with pytest.raises(ValueError):
        native.unpackb(b"\xdc\x00")          # truncated
    with pytest.raises(ValueError):
        native.unpackb(native.packb(1) + b"\x01")  # trailing bytes
    with pytest.raises(TypeError):
        native.packb(object())


def test_hostile_frames_rejected():
    """Wire hardening: crafted frames on the RPC port must error, not
    crash or allocate unboundedly (codec.cpp kMaxDepth / plausible())."""
    # deeply nested arrays: would C-stack-overflow without a depth cap
    deep = b"\x91" * 100_000 + b"\xc0"
    with pytest.raises(ValueError, match="nesting"):
        native.unpackb(deep)
    # a legitimate 512-deep... stays under the cap at 511
    ok = b"\x91" * 500 + b"\xc0"
    v = native.unpackb(ok)
    for _ in range(500):
        assert isinstance(v, list) and len(v) == 1
        v = v[0]
    assert v is None
    # 4-byte array header promising 2^32-1 elements with no payload:
    # must not preallocate a multi-GB list
    with pytest.raises(ValueError, match="length exceeds input"):
        native.unpackb(b"\xdd\xff\xff\xff\xff")
    # same for maps
    with pytest.raises(ValueError, match="length exceeds input"):
        native.unpackb(b"\xdf\xff\xff\xff\xff")
    # str/bin headers larger than the input
    with pytest.raises(ValueError):
        native.unpackb(b"\xdb\xff\xff\xff\xff" + b"x")
    with pytest.raises(ValueError):
        native.unpackb(b"\xc6\xff\xff\xff\xff" + b"x")


def test_fuzzed_roundtrips():
    import random
    rng = random.Random(42)

    def gen(depth=0):
        kinds = ["int", "str", "float", "none", "bool", "bytes"]
        if depth < 3:
            kinds += ["list", "dict"]
        k = rng.choice(kinds)
        if k == "int":
            return rng.randint(-2**40, 2**40)
        if k == "str":
            return "".join(chr(rng.randint(32, 0x2FF))
                           for _ in range(rng.randint(0, 40)))
        if k == "float":
            return rng.uniform(-1e6, 1e6)
        if k == "none":
            return None
        if k == "bool":
            return rng.random() < 0.5
        if k == "bytes":
            return bytes(rng.getrandbits(8)
                         for _ in range(rng.randint(0, 40)))
        if k == "list":
            return [gen(depth + 1) for _ in range(rng.randint(0, 8))]
        return {f"k{i}": gen(depth + 1)
                for i in range(rng.randint(0, 8))}

    for _ in range(200):
        v = gen()
        assert native.unpackb(native.packb(v)) == v
        assert msgpack.unpackb(native.packb(v), raw=False,
                               strict_map_key=False) == v


def test_rpc_frames_use_native_codec():
    """The RPC layer picks the native codec up transparently."""
    from nomad_tpu.rpc.codec import _default_backend
    dumps, _loads = _default_backend()
    assert dumps is native.packb


def test_throughput_sanity():
    """Not a benchmark gate — just confirms the native codec is in the
    same league as the C-accelerated msgpack on a typical RPC frame."""
    import time
    frame = [7, "Node.GetClientAllocs",
             {"allocs": [{"id": "x" * 36, "cpu": 500, "ok": True,
                          "states": {"web": {"state": "running",
                                             "restarts": 0}}}] * 50,
              "index": 12345}]
    n = 300
    t0 = time.perf_counter()
    for _ in range(n):
        native.unpackb(native.packb(frame))
    native_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        msgpack.unpackb(msgpack.packb(frame, use_bin_type=True),
                        raw=False)
    msgpack_s = time.perf_counter() - t0
    # within 5x of the reference C implementation
    assert native_s < msgpack_s * 5, (native_s, msgpack_s)
