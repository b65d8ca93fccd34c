#!/bin/sh
# Build libdpf_native.so (the CPU oracle kernels + ctypes C API) from
# the sources next to this script. Objects go to a private directory
# and the library lands by rename, so a concurrent loader never sees a
# half-written file.
set -e
cd "$(dirname "$0")"
work=$(mktemp -d ./.build.XXXXXX)
trap 'rm -rf "$work"' EXIT
# aesni.cc is the only unit built with -maes; callers gate on
# AesNiSupported() so the library still loads on machines without AES-NI.
g++ -O2 -fPIC -maes -std=c++17 -c aesni.cc -o "$work/aesni.o"
for unit in aes128 dpf_kernels keygen cuckoo_build; do
  g++ -O2 -fPIC -std=c++17 -c "$unit.cc" -o "$work/$unit.o"
done
g++ -shared -o "$work/libdpf_native.so" "$work"/*.o
mv -f "$work/libdpf_native.so" libdpf_native.so
echo "built $(pwd)/libdpf_native.so"
