"""The Pallas router-arbitration kernel and its pure-jnp oracle."""
