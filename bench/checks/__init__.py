"""One comparison with the plain reference per strategy, named by traffic."""
