#!/bin/sh
# Build libhetu_embed.so into $1 (default: build/libhetu_embed.so).  Called
# from hetu_tpu/embed/engine.py on first use and whenever the hash of these
# sources (or the CPU: -march=native) differs from the one stored beside
# the library.
set -e
cd "$(dirname "$0")"
out="${1:-../../build/libhetu_embed.so}"
mkdir -p "$(dirname "$out")"
g++ -O3 -march=native -fPIC -shared -std=c++17 -pthread \
    embed_engine.cpp ps_net.cpp -o "$out"
