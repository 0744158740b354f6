#!/bin/sh
# Build the native runtime components:
#   libballista_native.so   — shuffle row router (ctypes, no deps; no
#                             -march=native: the binary may be built on one
#                             host and loaded on another)
#   ballista-flight-server  — C++ Flight shuffle data plane (links the
#                             Arrow C++ shipped inside the pyarrow wheel)
# Each binary is built under a private name and renamed into place: several
# processes may find it missing and build at once (xdist workers, the
# executors of one host), and none may ever load a half-written file.
cd "$(dirname "$0")"
g++ -O3 -shared -fPIC -o "libballista_native.so.$$" row_router.cpp \
  && mv -f "libballista_native.so.$$" libballista_native.so
echo "built $(pwd)/libballista_native.so"

PYA="$(python -c 'import os, pyarrow; print(os.path.dirname(pyarrow.__file__))')"
AR_SO="$(ls "$PYA"/libarrow.so.* 2>/dev/null | head -1)"
FL_SO="$(ls "$PYA"/libarrow_flight.so.* 2>/dev/null | head -1)"
if [ -d "$PYA/include/arrow/flight" ] && [ -n "$AR_SO" ] && [ -n "$FL_SO" ]; then
  if g++ -std=c++20 -O2 -I"$PYA/include" flight_shuffle.cpp \
      -o "ballista-flight-server.$$" \
      -L"$PYA" -l:"$(basename "$AR_SO")" -l:"$(basename "$FL_SO")" \
      -Wl,-rpath,"$PYA" \
      && mv -f "ballista-flight-server.$$" ballista-flight-server; then
    echo "built $(pwd)/ballista-flight-server"
  else
    echo "flight server build failed (python data plane remains)" >&2
  fi
else
  echo "pyarrow flight headers/libs not found; skipping native flight server" >&2
fi
