"""Single-blob host->device transfer.

A multi-array upload ships ONE contiguous blob and reconstructs the
arrays device-side in ONE jitted (persistently compile-cached)
slice+bitcast call: one transfer and one dispatch, not one per array.
What a transfer costs on the current machine is not measured.

The blob dtype is int32, not uint8: every segment is stored as whole
4-byte words, so the device side needs only same-width bitcasts and
right-shifts, never a narrow->wide conversion; 2-byte dtypes are
widened host-side; bit-packed segments are exposed as uint32 words for
the caller to shift-unpack.

Reference analog: none — the reference's mgp graph view is
shared-memory.
"""

from __future__ import annotations

import numpy as np

_WORD = 4


def pack_blob(arrays: dict):
    """Concatenate host arrays into one contiguous int32-word blob.

    arrays values: numpy arrays of 4-byte dtypes (float32/int32/uint32),
    2-byte/1-byte ints or bool (widened host-side to int32), or the
    special form ("bits", uint8_array) for bit-packed payloads whose
    bytes are exposed device-side as uint32 words (trailing bytes of
    each row zero-padded to a word boundary).

    Returns (blob_i32, segments); segments[name] describes the layout
    for `unblob`.
    """
    segs = {}
    parts = []
    off = 0  # in words

    def add_words(name, words_i32, kind, shape, dtype):
        nonlocal off
        segs[name] = (off, words_i32.size, kind, shape, dtype)
        parts.append(words_i32)
        off += words_i32.size

    for name, arr in arrays.items():
        if isinstance(arr, tuple) and arr[0] == "bits":
            raw = np.ascontiguousarray(arr[1])
            if raw.dtype != np.uint8:
                raise TypeError(f"{name}: bits payload must be uint8")
            row_bytes = raw.shape[-1]
            pad = (-row_bytes) % _WORD
            if pad:
                raw = np.concatenate(
                    [raw, np.zeros(raw.shape[:-1] + (pad,), np.uint8)],
                    axis=-1)
            words = raw.reshape(-1).view(np.int32)
            add_words(name, words, "bits",
                      raw.shape[:-1] + (raw.shape[-1] // _WORD,), np.uint32)
            continue
        a = np.ascontiguousarray(arr)
        if a.dtype in (np.dtype(np.int16), np.dtype(np.uint16),
                       np.dtype(np.int8), np.dtype(np.uint8),
                       np.dtype(np.bool_)):
            widened = a.astype(np.int32)
            add_words(name, widened.reshape(-1), "cast", a.shape, a.dtype)
        elif a.dtype.itemsize == _WORD:
            add_words(name, a.reshape(-1).view(np.int32), "word",
                      a.shape, a.dtype)
        else:
            raise TypeError(f"{name}: unsupported dtype {a.dtype}")
    if not parts:
        raise ValueError("pack_blob: no arrays")
    return np.concatenate(parts), segs


def segs_key(segs: dict) -> tuple:
    """A blob's segment table as a hashable key: two blobs with equal
    keys are read by the same compiled program."""
    return tuple((name, off, n_words, kind, tuple(int(d) for d in shape),
                  np.dtype(dtype).str)
                 for name, (off, n_words, kind, shape, dtype)
                 in segs.items())


def segs_from_key(key: tuple) -> dict:
    """The segment table `unblob` reads, back from its `segs_key`."""
    return {name: (off, n_words, kind, shape, np.dtype(dtype))
            for name, off, n_words, kind, shape, dtype in key}


def unblob(blob, segs, name):
    """Traced: reconstruct one array from the int32-word device blob.

    "bits" segments come back as uint32 words; use `unpack_bit_words`
    to expand to 0/1 bits.
    """
    import jax
    import jax.numpy as jnp
    off, n_words, kind, shape, dtype = segs[name]
    raw = jax.lax.dynamic_slice_in_dim(blob, off, n_words)
    if kind == "cast":
        return raw.reshape(shape).astype(jnp.dtype(dtype))
    if kind == "bits":
        return jax.lax.bitcast_convert_type(raw, jnp.uint32).reshape(shape)
    if dtype == np.int32:
        return raw.reshape(shape)
    return jax.lax.bitcast_convert_type(
        raw, jnp.dtype(dtype)).reshape(shape)


def unpack_bit_words(words, n_bits):
    """Traced: (..., W) uint32 words -> (..., n_bits) bool.

    Bit i lives at word i>>5; within the word, bytes are little-endian
    and bits MSB-first per byte (numpy.packbits order): shift
    8*((i&31)>>3) + 7 - (i&7).
    """
    import jax.numpy as jnp
    j = np.arange(32)
    shifts = jnp.asarray(8 * (j >> 3) + 7 - (j & 7), dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)
    return bits[..., :n_bits] != 0


#: jitted reconstruction programs keyed by the blob's segment layout —
#: re-jitting a fresh closure per call silently retraced + recompiled on
#: EVERY upload (mglint MG008 recompile-hazard; the docstring's
#: "compile-cached per shape signature" promise was only true for the
#: persistent on-disk cache, not the in-process one)
_PREPARE_CACHE: dict = {}


def put_packed(arrays: dict) -> dict:
    """Ship `arrays` (dict of host np arrays) in one transfer; returns a
    dict of device arrays (one jitted reconstruction call, compile-cached
    per shape signature)."""
    import jax
    from ..utils.jax_cache import ensure_compile_cache
    ensure_compile_cache()

    blob_np, segs = pack_blob(arrays)
    key = segs_key(segs)
    prepare = _PREPARE_CACHE.get(key)
    if prepare is None:
        @jax.jit
        def prepare(blob, _segs=segs):
            return {name: unblob(blob, _segs, name) for name in _segs}

        _PREPARE_CACHE[key] = prepare

    return prepare(jax.device_put(blob_np))
