#!/bin/sh
# Build the native kernels into the Python package.
#
# Usage:
#   build.sh                         release build (-O3/-O2)
#   build.sh --sanitize=address,undefined
#                                    ASan+UBSan instrumented .so's (-O1 -g,
#                                    frame pointers kept for usable reports)
#   build.sh --sanitize=thread       TSan instrumented .so's — covers the
#                                    dmkern row-parallel pthread pool
#
# Sanitized builds overwrite the same detectmateservice_tpu/_native/*.so
# paths the bindings load, so the Python test suite exercises the
# instrumented code directly; scripts/native_sanitize.sh drives the full
# build→test→rebuild-clean cycle (and CI's native-sanitize job runs it).
# The host process must preload the matching runtime (libasan/libtsan) —
# the runner script handles that too.
set -e
cd "$(dirname "$0")"
mkdir -p ../detectmateservice_tpu/_native

SANITIZE=""
for arg in "$@"; do
    case "$arg" in
        --sanitize=*) SANITIZE="${arg#--sanitize=}" ;;
        *) echo "unknown argument: $arg" >&2; exit 2 ;;
    esac
done

# Sanitizer flag sets: -O1 + frame pointers for attributable stacks; the
# release build keeps its full optimization levels.
SAN_CFLAGS=""
KERN_OPT="-O3"
TRANS_OPT="-O2"
case "$SANITIZE" in
    "") ;;
    thread)
        SAN_CFLAGS="-fsanitize=thread -fno-omit-frame-pointer -g"
        KERN_OPT="-O1"; TRANS_OPT="-O1" ;;
    address|undefined|address,undefined|undefined,address)
        SAN_CFLAGS="-fsanitize=$SANITIZE -fno-omit-frame-pointer -g"
        KERN_OPT="-O1"; TRANS_OPT="-O1" ;;
    *) echo "unsupported --sanitize=$SANITIZE (use address,undefined or thread)" >&2
       exit 2 ;;
esac
[ -n "$SANITIZE" ] && echo "sanitized build: $SANITIZE"

CC="${CC:-cc}"
# Stamp the feature version the Python bindings expect — the one staleness
# rule this script shares with the import-time builds in utils/matchkern.py
# and engine/native_transport.py: a library reporting a different number is
# rebuilt at import. The C sources default to the same numbers for bare
# `cc` builds.
KVER=$(sed -n 's/^DM_FEATURE_VERSION = \([0-9][0-9]*\).*/\1/p' \
    ../detectmateservice_tpu/utils/matchkern.py)
$CC $KERN_OPT -shared -fPIC -pthread $SAN_CFLAGS \
    ${KVER:+-DDM_FEATURE_VERSION=$KVER} \
    -o ../detectmateservice_tpu/_native/libdmkern.so matchkern/dmkern.c
echo "built detectmateservice_tpu/_native/libdmkern.so (feature version ${KVER:-default}${SANITIZE:+, sanitize=$SANITIZE})"
if [ -f transport/dmtransport.cpp ]; then
    CXX="${CXX:-c++}"
    TVER=$(sed -n 's/^DMT_FEATURE_VERSION = \([0-9][0-9]*\).*/\1/p' \
        ../detectmateservice_tpu/engine/native_transport.py)
    # link the soname directly: this image ships libzmq.so.5 without the
    # -lzmq dev symlink or header (the ABI is declared in the .cpp)
    $CXX $TRANS_OPT -std=c++17 -shared -fPIC $SAN_CFLAGS \
        ${TVER:+-DDMT_FEATURE_VERSION=$TVER} \
        -o ../detectmateservice_tpu/_native/libdmtransport.so \
        transport/dmtransport.cpp -l:libzmq.so.5 -lpthread
    echo "built detectmateservice_tpu/_native/libdmtransport.so (feature version ${TVER:-default}${SANITIZE:+, sanitize=$SANITIZE})"
fi
