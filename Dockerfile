# Agent image: thin host plane + TPU analytics plane.
# The eBPF object is built in a stage with clang; the runtime stage stays slim.

FROM debian:bookworm-slim AS bpf-build
RUN apt-get update && apt-get install -y --no-install-recommends \
    clang llvm make cmake g++ libbpf-dev && rm -rf /var/lib/apt/lists/*
WORKDIR /src
COPY netobserv_tpu/datapath ./netobserv_tpu/datapath
RUN cmake -S netobserv_tpu/datapath/native -B build -DDATAPATH_BPF=ON \
    && cmake --build build || echo "bpf object skipped (no vmlinux.h)"
RUN g++ -O2 -Wall -shared -fPIC netobserv_tpu/datapath/native/flowpack.cc \
    -o libflowpack.so

FROM python:3.12-slim
RUN pip install --no-cache-dir "jax[tpu]" numpy grpcio protobuf \
    prometheus_client orbax-checkpoint pyyaml
WORKDIR /app
COPY netobserv_tpu ./netobserv_tpu
COPY proto ./proto
COPY __graft_entry__.py ./
COPY --from=bpf-build /src/libflowpack.so \
     ./netobserv_tpu/datapath/native/build/libflowpack.so
COPY --from=bpf-build /src/build/flowpath.bpf.o* \
     ./netobserv_tpu/datapath/native/build/
ENTRYPOINT ["python", "-m", "netobserv_tpu"]
