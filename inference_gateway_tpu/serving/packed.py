"""Packed step descriptors (ISSUE 25): one host buffer, one upload.

A host-stepped engine submit (the mixed step, the fresh decode chunk)
hands its program a dozen small descriptor arrays. Uploaded one by one
each costs a host→device transfer with the device waiting; packed they
are ONE int32 buffer whose layout is fixed when the engine is built, so
the host writes its fields through views and the program slices them
apart again with static offsets.

Every field is stored as int32 words, back to back in declaration
order: ``"i32"`` as is, ``"bool"`` as 0/1, ``"f32"`` by bit pattern
(the host view is a float32 view of the same words; the program
bitcasts them back, so no value is rounded on the way).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_KINDS = ("i32", "f32", "bool")


class PackedLayout:
    """Field offsets of one packed descriptor buffer.

    ``fields`` is a sequence of ``(name, shape, kind, fill)``; a field
    of zero elements is legal (dense engines have no page table).
    ``host`` is the engine's staging buffer and ``views`` its per-field
    numpy views; ``begin()`` resets it to the fill values and
    ``snapshot()`` is what gets uploaded — a private copy, because the
    runtime may read (CPU: alias) the staged array after the submit
    returns and the next submit rewrites the views.
    """

    def __init__(self, fields) -> None:
        self.fields: dict[str, tuple[int, int, tuple[int, ...], str]] = {}
        off = 0
        for name, shape, kind, _fill in fields:
            assert kind in _KINDS, kind
            size = int(np.prod(shape, dtype=np.int64))
            self.fields[name] = (off, off + size, tuple(shape), kind)
            off += size
        self.size = off
        self.host = np.zeros((off,), np.int32)
        # Per-field views of the staging buffer (bool fields hold 0/1).
        self.views: dict[str, np.ndarray] = {}
        for name, _shape, _kind, fill in fields:
            a, b, shape, kind = self.fields[name]
            words = self.host[a:b]
            if kind == "f32":
                words = words.view(np.float32)
            self.views[name] = words.reshape(shape)
            self.views[name][...] = fill
        self._blank = self.host.copy()

    def begin(self) -> dict[str, np.ndarray]:
        self.host[:] = self._blank
        return self.views

    def snapshot(self) -> np.ndarray:
        return self.host.copy()

    def unpack(self, buf: jax.Array) -> dict[str, jax.Array]:
        """Inside a jitted program: the fields of an uploaded buffer,
        sliced at static offsets and returned in their device dtypes."""
        out = {}
        for name, (a, b, shape, kind) in self.fields.items():
            words = buf[a:b]
            if kind == "f32":
                words = jax.lax.bitcast_convert_type(words, jnp.float32)
            elif kind == "bool":
                words = words != 0
            out[name] = words.reshape(shape)
        return out
