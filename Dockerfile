# syntax=docker/dockerfile:1.3
# Shape mirrors the reference's worldql_server.Dockerfile: a build
# stage producing the native artifacts, a slim non-root runtime, the
# three default service ports exposed.

# ---
# Build Time
FROM python:3.12-slim AS builder

RUN apt-get update && \
  apt-get install --no-install-recommends -y \
    g++ \
    make \
    git && \
  rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY native ./native
COPY worldql_server_tpu ./worldql_server_tpu

# Native wire codec (pure-Python fallback exists, but ship the fast path)
RUN make -C native

# dependency versions: pyproject.toml states the stack (jax 0.9.0) once
RUN pip install --no-cache-dir --prefix=/install .

# ---
# Runtime
FROM python:3.12-slim
WORKDIR /

# Setup non-root user
RUN \
  groupadd -g 1001 worldql && \
  useradd -mu 1001 -g worldql worldql

COPY --from=builder --chown=1001:1001 /install /usr/local
COPY --from=builder --chown=1001:1001 /app/native/libwqlcodec.so /opt/worldql/native/libwqlcodec.so
ENV WQL_NATIVE_CODEC=/opt/worldql/native/libwqlcodec.so

# Stamp the build's git hash for --version (build.rs:4-11 parity);
# docker build --build-arg WQL_GIT_HASH=$(git rev-parse --short HEAD).
# Runtime stage only — a changed hash must not bust the builder's
# dependency-install layer cache.
ARG WQL_GIT_HASH=
ENV WQL_GIT_HASH=${WQL_GIT_HASH}

# Define repo label
ARG GIT_REPO
LABEL org.opencontainers.image.source=${GIT_REPO}

# Expose default ports: ZeroMQ, HTTP, WebSocket
EXPOSE 5555
EXPOSE 8080
EXPOSE 8081

# Records default to an in-container sqlite file the non-root user can
# write; override WQL_STORE_URL for anything durable.
ENV WQL_STORE_URL=sqlite:///home/worldql/worldql.db

# Compiled device programs persist here across restarts of a container
# that keeps its home (mount a volume to keep them across containers):
# an installed package has no writable checkout for the default
# .jax_cache, and a cold boot with a large index compiles for minutes.
ENV JAX_COMPILATION_CACHE_DIR=/home/worldql/.cache/jax

# Define user and entrypoint
USER worldql
ENTRYPOINT ["worldql-server-tpu"]
