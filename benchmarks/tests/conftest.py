import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
# the node child of a test run is a CPU node on purpose
os.environ["JAX_PLATFORMS"] = "cpu"
