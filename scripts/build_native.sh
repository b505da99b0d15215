#!/usr/bin/env bash
# Build the native (C++) extensions ahead of time, for THIS host:
#   roaring_codec  (fragment-file codec, PR 5)
#   sparse_merge   (bulk-ingest merge kernels)
#
# The ctypes loader (pilosa_tpu/native/__init__.py) builds lazily on
# first use and only loads lib<name>.<key>.so, keyed on source + its
# build flags + this host's CPU; this script writes to exactly that
# path, so what it builds is what the loader finds here (and nowhere
# else).  It exists for CI images and for debugging:
#
#   scripts/build_native.sh           # -O2 -Wall (warnings are errors)
#   scripts/build_native.sh --asan    # AddressSanitizer debug build
#
# Without a C++ toolchain the loader degrades to the pure-numpy paths,
# which stay bit-exact with the native kernels (tests/test_native_merge.py
# exercises both).
set -euo pipefail

cd "$(dirname "$0")/.."
NATIVE_DIR=pilosa_tpu/native

CXX=${CXX:-g++}
FLAGS=(-O2 -Wall -Werror -shared -fPIC -std=c++17)
if [[ "${1:-}" == "--asan" ]]; then
    FLAGS+=(-g -fsanitize=address -fno-omit-frame-pointer)
    echo "ASan build: run python with LD_PRELOAD=\$($CXX -print-file-name=libasan.so)" >&2
fi

for name in roaring_codec sparse_merge; do
    src="$NATIVE_DIR/$name.cpp"
    out=$(python3 -c "from pilosa_tpu import native; print(native._lib_path('$name', '$src'))")
    echo "building $out"
    "$CXX" "${FLAGS[@]}" -o "$out" "$src"
done
echo "done"
