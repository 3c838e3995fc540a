"""repro: a jax/pallas reproduction of "TensorFlow: A system for
large-scale machine learning" grown toward a production serving/training
stack.
"""
