#!/bin/sh
# Build the native tpurecord reader. Invoked automatically by
# tpucfn/data/native.py on first use; safe to run by hand.
#
#   sh build.sh          optimized build
#   sh build.sh --tsan   ThreadSanitizer build (race-detection CI lane for
#                        the concurrent-reader contract; SURVEY.md §5)
set -e
cd "$(dirname "$0")"
if [ "$1" = "--tsan" ]; then
  g++ -O1 -g -fsanitize=thread -fPIC -shared -std=c++17 -Wall \
      -o libtpurecord_tsan.so tpurecord.cc -lz
  echo "built $(pwd)/libtpurecord_tsan.so (ThreadSanitizer)"
else
  # Build beside the target and rename: a concurrent loader (xdist
  # workers, launch ranks) never dlopens a half-written library.
  g++ -O3 -fPIC -shared -std=c++17 -Wall -o "libtpurecord.so.$$" tpurecord.cc -lz
  mv -f "libtpurecord.so.$$" libtpurecord.so
  echo "built $(pwd)/libtpurecord.so"
fi
