"""repro — production JAX framework for NL-ADC analog in-memory computing.

Reproduction + TPU-native extension of "Efficient Nonlinear Function
Approximation in Analog Resistive Crossbars for Recurrent Neural Networks".
"""

__version__ = "1.0.0"
