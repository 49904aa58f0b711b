from setuptools import find_packages, setup

setup(
    name="distkeras-tpu",
    version="0.1.0",
    description=(
        "TPU-native distributed deep learning: the dist-keras trainer/"
        "transformer/predictor API on JAX/XLA meshes instead of Spark"
    ),
    long_description=open("README.md", encoding="utf-8").read(),
    long_description_content_type="text/markdown",
    license="MIT",
    packages=find_packages(include=["distkeras_tpu", "distkeras_tpu.*"]),
    python_requires=">=3.10",
    # the versions the code is written and tested against (utils/compat.py
    # calls jax.shard_map / lax.axis_size directly — no older spelling)
    install_requires=[
        "jax>=0.9.0",
        "jaxlib>=0.9.0",
        "flax>=0.12.3",
        "optax>=0.2.6",
        "numpy",
    ],
    extras_require={
        "keras": ["keras>=3.0"],
        "checkpoint": ["orbax-checkpoint"],
        "test": ["pytest", "chex"],
    },
    classifiers=[
        "Programming Language :: Python :: 3",
        "Topic :: Scientific/Engineering :: Artificial Intelligence",
    ],
)
