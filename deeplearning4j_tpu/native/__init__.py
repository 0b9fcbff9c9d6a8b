"""Native C++ IO runtime bindings.

The reference reaches native code through JavaCPP JNI (SURVEY §2.1); here the
host-side data-pipeline hot loops (IDX/CSV decode, u8→f32 normalization,
batch gather) live in C++ (native/src/io.cpp) behind a flat C ABI loaded via
ctypes. ctypes releases the GIL during calls, so decode overlaps Python-side
work and XLA compute. Everything has a numpy fallback — the native lib is an
accelerator, not a dependency.
"""

from deeplearning4j_tpu.native.io import (  # noqa: F401
    native_available, read_idx, read_csv, u8_to_f32, gather_rows,
)


def library_origins() -> dict:
    """Load every native library and report how each was obtained:
    ``{so_name: "built" | "prebuilt" | "unavailable"}`` (see
    ``_loader.NativeLib.origin``) — what a bring-up run prints so a
    numpy fallback is never silent."""
    from deeplearning4j_tpu.native import image, io, word2vec
    libs = (io._NATIVE, image._NATIVE, word2vec._LIB)
    for lib in libs:
        lib.load()
    return {lib.so_name: lib.origin for lib in libs}
