"""Object-layer base plumbing: RObject idiom + camelCase compatibility.

→ org/redisson/RedissonObject.java (name addressing, delete/rename/exists)
and org/redisson/api/RObject.java.  Java users call ``tryInit``/``addAll``;
we expose snake_case Python APIs and transparently alias camelCase so the
reference API shape survives verbatim.
"""

from __future__ import annotations

import re

import numpy as np

from redisson_tpu.codecs import encode_batch
from redisson_tpu.utils import hashing

_CAMEL_RE = re.compile(r"(?<!^)(?=[A-Z])")


def camel_to_snake(name: str) -> str:
    return _CAMEL_RE.sub("_", name).lower()


class CamelCompatMixin:
    """bloomFilter.tryInit(...) works exactly like bloom_filter.try_init."""

    def __getattr__(self, item):
        if not item.startswith("_"):
            snake = camel_to_snake(item)
            if snake != item:
                try:
                    # getattr (not object.__getattribute__) so snake-case
                    # names served by a subclass __getattr__ — e.g. the
                    # synthesized *_async forms — resolve for camelCase too
                    # (putAsync → put_async).  No recursion: snake != item.
                    return getattr(self, snake)
                except AttributeError:
                    pass
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {item!r}"
        )


class MappedFuture:
    """Future adapter applying a transform on .result() — used by the
    deferred (batch-pipelined) forms of sync-named methods."""

    def __init__(self, fut, transform):
        self._fut = fut
        self._transform = transform

    def result(self, *a, **kw):
        return self._transform(self._fut.result(*a, **kw))

    get = result

    def done(self):
        return self._fut.done()


class CompletedFuture:
    """Already-resolved future (RFuture parity for host-grid ops)."""

    def __init__(self, value):
        self._value = value

    def result(self, *a, **kw):
        return self._value

    get = result

    @staticmethod
    def done():
        return True


class RObject(CamelCompatMixin):
    """Name-addressed object bound to a client engine.

    ``_DEFERRED`` maps sync-named methods to attributes returning a future
    whose resolved value matches the SYNC return contract — the batch
    facade routes queued sync calls through these so a natural batch
    pipelines instead of executing sequentially (SURVEY.md §3.4)."""

    KIND: str = ""
    _DEFERRED: dict = {}

    def __init__(self, name: str, client):
        self._name = name
        self._client = client
        self._engine = client._engine
        self._codec = client.config.codec

    def get_name(self) -> str:
        return self._name

    @property
    def name(self) -> str:
        return self._name

    def is_exists(self) -> bool:
        return self._engine.exists(self._name)

    def delete(self) -> bool:
        return self._engine.delete(self._name)

    def rename(self, new_name: str) -> None:
        if not self._engine.rename(self._name, new_name):
            # Failed rename (missing/expired source) must NOT repoint the
            # handle — it would silently start mutating whatever already
            # lives under new_name.
            raise RuntimeError(f"object {self._name!r} does not exist")
        self._name = new_name

    # -- expiry (→ org/redisson/RedissonExpirable.java) --------------------

    def expire(self, ttl_s: float) -> bool:
        """Schedule deletion ``ttl_s`` seconds from now (EXPIRE)."""
        return self._engine.expire(self._name, ttl_s)

    def expire_at(self, timestamp: float) -> bool:
        """Absolute-deadline expiry (EXPIREAT, unix seconds)."""
        return self._engine.expire_at(self._name, timestamp)

    def clear_expire(self) -> bool:
        """Remove a pending TTL (PERSIST)."""
        return self._engine.clear_expire(self._name)

    def remain_time_to_live(self) -> int:
        """Remaining TTL in ms; -1 no TTL, -2 absent (PTTL)."""
        return self._engine.remain_ttl_ms(self._name)

    # -- dump/restore (→ org/redisson/RedissonObject.java#dump) ------------

    def dump(self) -> bytes:
        """Opaque serialized state (DUMP); raises if absent."""
        data = self._engine.dump(self._name)
        if data is None:
            raise RuntimeError(f"object {self._name!r} does not exist")
        return data

    def restore(self, data: bytes, replace: bool = False) -> None:
        """Recreate this object from ``dump`` bytes (RESTORE)."""
        self._engine.restore(self._name, data, replace=replace)

    # -- hashing helpers shared by sketch objects --------------------------

    def _encode(self, objs) -> tuple[np.ndarray, np.ndarray]:
        """Key encoding, the one site the API and the RESP door share:
        timed into ``rtpu_encode_seconds{kind}`` and named ``rtpu:encode``
        in a capture, once per call."""
        if np.isscalar(objs) or isinstance(objs, (str, bytes)):
            objs = [objs]
        with self._engine.obs.encode_timer(self.KIND or "object"):
            return encode_batch(self._codec, objs)

    def _hash_lanes(self, objs):
        blocks, lengths = self._encode(objs)
        return hashing.murmur3_x86_128(blocks, lengths)

    def _hash128(self, objs):
        blocks, lengths = self._encode(objs)
        return hashing.hash128_np(blocks, lengths)
