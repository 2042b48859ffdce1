"""Test only: a replica whose timed path is broken underneath. Every
fourth token the engine samples is altered where it is produced (the
shared one-row sampler), then the program's server runs as usual.
``correct`` must come out false."""

import sys


def main(argv=None) -> int:
    from benchmark.workers import traced_replica
    from kubeflow_tpu.models import generate

    real = generate._sample

    def altered(logits, key, temperature, top_k):
        import jax.numpy as jnp

        tok = real(logits, key, temperature, top_k)
        return jnp.where(tok % 4 == 0, (tok + 1) % logits.shape[-1], tok)

    generate._sample = altered
    return traced_replica.main(argv)


if __name__ == "__main__":
    sys.exit(main())
