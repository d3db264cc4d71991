"""Dispatch programs kept as loaded executables beside JAX's compile cache.

JAX's persistent cache is keyed by the LOWERED module, so every boot traces
and lowers each program (the whole model, every Pallas kernel's jaxpr ->
Mosaic -> serialized module) only to compute the name under which the cache
then finds the executable: 1.2-3.6 s a program on the chip's host, half of
a warm boot's warm-up and more (PERF.md section 6, PR 57). A store holds
each program's serialized executable with its argument and result trees
(``jax.experimental.serialize_executable``) under a key the runner computes
without tracing (``ModelRunner._program_store``), and a boot whose key
matches loads them (docs/ELASTIC.md, fast-start).

Files, all in the compile-cache directory and none ending in ``-cache`` or
``-atime`` (JAX's size-capped cache counts and evicts only those):

* ``pstpu-warmup-<key>.ok``: the manifest, written when a boot has run
  every variant; a JSON list of the programs stored under the key;
* ``pstpu-program-<key>-<program>.bin``: one program, a compressed pickle
  of ``(payload, in_tree, out_tree)`` (zstandard where it is installed, as
  JAX's own cache entries are, else zlib: a TPU executable of 18 MB keeps 5).

A stored program is a pickle and is trusted exactly as the directory's
compiled programs are: whoever writes the cache directory chooses the code
the engine runs.
"""

import functools
import hashlib
import json
import os
import pickle
import re
import zlib
from typing import FrozenSet, Iterable, Optional


@functools.lru_cache(maxsize=1)
def source_digest() -> str:
    """A digest of this package's source (every ``*.py`` under
    ``production_stack_tpu``, path and contents): what tracing used to
    notice for free. Milliseconds, once a process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.blake2b(digest_size=12)
    for directory, subdirs, files in os.walk(root):
        subdirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(directory, name)
                digest.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    digest.update(f.read())
    return digest.hexdigest()


_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


def _compress(data: bytes) -> bytes:
    try:
        import zstandard
    except ImportError:
        return zlib.compress(data, 1)
    return zstandard.ZstdCompressor().compress(data)


def _decompress(data: bytes) -> bytes:
    if data[:4] == _ZSTD_MAGIC:
        import zstandard

        return zstandard.ZstdDecompressor().decompress(data)
    return zlib.decompress(data)


class ProgramStore:
    """The stored programs of ONE key in one directory."""

    def __init__(self, directory: str, doc: dict):
        self.directory = directory
        self.key = hashlib.blake2b(
            json.dumps(doc, sort_keys=True, default=str).encode(),
            digest_size=12,
        ).hexdigest()
        self.manifest_path = os.path.join(
            directory, f"pstpu-warmup-{self.key}.ok")

    def path(self, program_key: str) -> str:
        name = re.sub(r"[^A-Za-z0-9_.]+", "-", program_key).strip("-")
        return os.path.join(
            self.directory, f"pstpu-program-{self.key}-{name}.bin")

    # ------------------------------------------------------------- manifest
    def manifest(self) -> Optional[FrozenSet[str]]:
        """The programs a complete boot stored under this key; None where
        no such boot has been (or its manifest cannot be read)."""
        try:
            with open(self.manifest_path) as f:
                return frozenset(json.load(f)["programs"])
        except (OSError, ValueError, KeyError, TypeError):
            return None

    def write_manifest(self, programs: Iterable[str]) -> None:
        self._write(self.manifest_path,
                    json.dumps({"programs": sorted(programs)}).encode())

    def drop_manifest(self) -> None:
        try:
            os.unlink(self.manifest_path)
        except OSError:
            pass

    # ------------------------------------------------------------- programs
    def save(self, program_key: str, compiled) -> None:
        """Store a ``jax.stages.Compiled``. Raises where the executable
        does not serialize (a program that closes over device arrays) or
        the file cannot be written."""
        from jax.experimental import serialize_executable

        self._write(self.path(program_key), _compress(
            pickle.dumps(serialize_executable.serialize(compiled))))

    def load(self, program_key: str, devices):
        """The stored program as a loaded ``Compiled`` on ``devices`` (the
        mesh's, in its order). Raises where the file is missing, short or
        refused by the backend."""
        from jax.experimental import serialize_executable

        with open(self.path(program_key), "rb") as f:
            payload, in_tree, out_tree = pickle.loads(_decompress(f.read()))
        return serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree, backend=devices[0].client,
            execution_devices=devices)

    @staticmethod
    def _write(path: str, data: bytes) -> None:
        # Whole or not at all: a boot killed mid-write leaves no short file
        # under a name a later boot would read.
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
